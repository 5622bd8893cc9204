"""Benchmark suite — generates the numbers BASELINE.md says this rebuild
must produce (the reference publishes none; see BASELINE.md).

Configs (BASELINE.json "eval" list):

- ``demo``     — the reference's only in-repo baseline: CoCoA+ on
  data/small_train.dat (n=2000, d=9947, K=4, H=50, λ=1e-3,
  run-demo-local.sh:2-9), wall-clock + comm-rounds to a 1e-4 duality gap.
- ``epsilon``  — epsilon-like dense synthetic (400K×2000, unit rows,
  data/synth.py), K=8, H=0.1·n/K, λ=1e-3, to 1e-4 gap.
- ``rcv1``     — rcv1.binary-like sparse synthetic (20242×47236, ~75
  nnz/row), K=8, H=0.1·n/K, λ=1e-4, to 1e-3 and 1e-4 gaps.
- ``mbcd-rcv1`` / SGD-family / DistGD rows — the remaining reference
  algorithms on the same data (fixed round budgets; they have no duality-
  gap certificate to target — SGD/DistGD are primal-only, and mini-batch
  CD's β/(K·H) scaling makes gap progress per round much slower than
  CoCoA's, exactly the point the CoCoA papers make).  All six reference
  algorithms (hingeDriver.scala:84-110) have a row.
- ``lasso`` / ``elastic`` — ProxCoCoA+ on the L1 / L1+L2 objectives.

**Timing is slope-measured** (VERDICT r2 item 2): the raw wall-clock of a
run carries a fixed dispatch+fetch cost, noisy run to run and larger than
many whole configs.  For each config the gap-targeted
run determines the round count R (and verifies the certificate); two
fixed-round runs at R and m·R then give per_round = (T(mR) − T(R))/((m−1)R),
``wallclock_s`` = per_round·R (the steady state), and ``fixed_s`` =
T(R) − wallclock_s (the dispatch overhead, reported separately).  m is
sized so the span dominates the noise.  ``--quick`` shrinks the synthetic
sizes ~10x for smoke-testing the suite.

The ``vs_oracle`` column is the speedup over the literal NumPy oracle of
the Scala update rules (tests/oracle.py) executing the same number of
rounds single-threaded — measured directly for the demo config and
extrapolated from a few oracle rounds at the big scales (the oracle is
the reference's *math* without Spark overhead, so this flatters the
reference).  Permuted-sampling rows reach the same certified gap in
FEWER rounds; their cross-mode speedup (oracle at reference-mode rounds
vs the permuted run's wall-clock) is reported in a separate
``vs_oracle_same_gap`` column so ``vs_oracle`` keeps one meaning
(ADVICE r2).

Writes one JSON line per config to benchmarks/results.jsonl, a markdown
table to benchmarks/RESULTS.md, and regenerates the marked perf blocks
in BASELINE.md and PARITY.md from the same rows (one source of truth).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tests"))

from cocoa_tpu.utils import compile_cache

compile_cache.enable()   # persistent XLA cache: regen compiles once, ever

_REPO_DATA = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "data")
_REF_DATA = "/root/reference/data"


def _demo_file(name):
    # per-file probe: a partial reference checkout falls back to the
    # identical committed twin (same rule as tests/conftest.py, bench.py)
    ref = os.path.join(_REF_DATA, name)
    return ref if os.path.exists(ref) else os.path.join(_REPO_DATA, name)


DEMO_TRAIN = _demo_file("small_train.dat")
DEMO_TEST = _demo_file("small_test.dat")
DEMO_D = 9947

# published shapes of the real datasets (the integrity pin the air-gapped
# build CAN carry — see benchmarks/fetch_data.sh for the sha256 story).
# (n, d, nnz/row range): epsilon is dense (exactly d per row); rcv1's
# published average is ~73.2 cosine-normalized tf-idf terms per document
# (ADVICE r3: shape alone passes for any same-line-count file — also pin
# density, which a corrupted/wrong file of the same n would not match).
REAL_SHAPES = {
    "rcv1_train.binary": (20_242, 47_236, (60.0, 90.0)),
    "epsilon_normalized": (400_000, 2_000, (2000.0, 2000.0)),
}


def _maybe_real(data_dir, fname):
    """Load benchmarks/data/<fname> when present (fetched by
    fetch_data.sh), validating the published (n, d) shape and nnz/row
    density; None when absent (the synthetic stand-in is used and labeled
    as such)."""
    path = os.path.join(data_dir, fname)
    if not os.path.exists(path):
        return None
    from cocoa_tpu.data import load_libsvm

    n_want, d_want, (nz_lo, nz_hi) = REAL_SHAPES[fname]
    data = load_libsvm(path, d_want)
    nnz_row = len(data.values) / max(1, data.n)
    if data.n != n_want or not (nz_lo <= nnz_row <= nz_hi):
        raise ValueError(
            f"{path}: expected the published shape n={n_want} (d={d_want}) "
            f"with {nz_lo}-{nz_hi} nnz/row, parsed n={data.n} "
            f"nnz/row={nnz_row:.1f} — corrupt or wrong file"
        )
    print(f"using real dataset {fname}: n={data.n} d={d_want} "
          f"nnz/row={nnz_row:.1f}")
    return data


def _dense_subsample(data, n_sub):
    """(X, y) dense NumPy arrays of the first n_sub rows (oracle input)."""
    X = np.zeros((n_sub, data.num_features))
    for i in range(n_sub):
        lo, hi = data.indptr[i], data.indptr[i + 1]
        X[i, data.indices[lo:hi]] = data.values[lo:hi]
    return X, data.labels[:n_sub].astype(np.float64)


from slope import slope_time as _slope_time  # noqa: E402


def _timed(make_run, rounds, **kw):
    """(steady_s, fixed_s, quality-dict) — rows carry ``noisy``/``span_s``
    when the slope escalation exited without the span dominating the
    run-to-run jitter (ADVICE r3: a degraded measurement must not look like a
    clean one; the round-3 rcv1-permuted anomaly had that signature)."""
    sr = _slope_time(make_run, rounds, **kw)
    q = ({"noisy": True, "span_s": round(sr.span_s, 3)}
         if sr.degraded else {})
    return sr.steady_s, sr.fixed_s, q


def _perf(tag, secs, rounds, *, n, d, k, h, layout="dense", nnz=None,
          path="fast", block=0, debug_iter=10, test_n=0):
    """Fold a measured run into the perf-accounting columns (benchmarks/
    perf.py): FLOP model, achieved FLOP/s, MFU, µs per coordinate step,
    HBM floor, and the roofline bound classification."""
    import perf

    model = perf.sdca_round_model(n, d, k, h, layout=layout, nnz=nnz,
                                  path=path, block=block)
    return perf.account(
        tag, secs / max(1, rounds), model, steps=k * h,
        evals_per_round=1.0 / debug_iter,
        eval_fl=perf.eval_flops(n, d, nnz=nnz, test_n=test_n),
    )


def _round_rate(run_round, rounds, reps=3):
    """rounds/sec of ``run_round(t)`` (t 1-based), with round 1 executed
    as an UNTIMED warm-up: the first NumPy round pays allocation/BLAS
    warm-up and a 2-3 round window would otherwise overstate vs_oracle
    ~3x vs the pinned bench.py rate.

    BEST of ``reps`` windows: single-thread NumPy timing swings ~2x with
    concurrent host load (observed across same-day regens: identical
    rcv1 configs read 13.2x and 9.3x vs_oracle purely from oracle-rate
    noise), and the best window is the least-contended — i.e. the
    fairest — estimate of the oracle's true speed."""
    run_round(1)
    t = 2
    best = None
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(rounds):
            run_round(t)
            t += 1
        dt = time.perf_counter() - t0
        best = dt if best is None or dt < best else best
    return rounds / best


def _oracle_rounds_per_s_csr(data, lam, h, k, n, rounds=2, mode="plus"):
    """Single-thread oracle round rate on a SPARSE problem, from the raw
    CSR arrays — the literal per-step math (sparse dot, box projection,
    sparse axpy) without ever densifying X.  Fills the vs_oracle cells the
    r1 benchmarks left empty (dense oracle needs n×d memory)."""
    from cocoa_tpu.data.sharding import split_sizes
    from cocoa_tpu.utils.prng import sample_indices

    sizes = split_sizes(n, k)
    offs = np.concatenate([[0], np.cumsum(sizes)])
    indptr, indices, values, y = (data.indptr, data.indices, data.values,
                                  data.labels)
    d = data.num_features
    w = np.zeros(d)
    alphas = [np.zeros(sizes[s]) for s in range(k)]
    sigma = float(k)
    plus = mode == "plus"
    lam_n = lam * n

    def step(t):
        nonlocal w
        dw_sum = np.zeros(d)
        for s in range(k):
            idxs = sample_indices(0, range(t, t + 1), h, sizes[s])[0]
            a = alphas[s]
            dw = np.zeros(d)
            # "cocoa": each worker advances a PRIVATE copy of w (the
            # reference ships w in the task closure, CoCoA.scala:142,183);
            # the local advances are discarded, only dw is reduced
            wl = w.copy() if mode == "cocoa" else w
            for li in idxs:
                gi = offs[s] + li
                cols = indices[indptr[gi]:indptr[gi + 1]]
                vals = values[indptr[gi]:indptr[gi + 1]]
                yy = y[gi]
                if plus:
                    grad = (yy * (vals @ w[cols] + sigma * (vals @ dw[cols]))
                            - 1.0) * lam_n
                else:  # "cocoa" (locally-advancing wl) and "frozen" (MbCD)
                    grad = (yy * (vals @ wl[cols]) - 1.0) * lam_n
                proj = grad
                if a[li] <= 0.0:
                    proj = min(grad, 0.0)
                elif a[li] >= 1.0:
                    proj = max(grad, 0.0)
                if proj != 0.0:
                    qii = float(vals @ vals) * (sigma if plus else 1.0)
                    new_a = 1.0 if qii == 0.0 else min(
                        max(a[li] - grad / qii, 0.0), 1.0)
                    coef = yy * (new_a - a[li]) / lam_n
                    dw[cols] += coef * vals
                    if mode == "cocoa":
                        wl[cols] += coef * vals
                    a[li] = new_a
            dw_sum += dw
        w = w + dw_sum  # gamma=1 additive

    return _round_rate(step, rounds)


def _oracle_rounds_per_s(ds_like, lam, h, k, n, rounds=3):
    """Single-thread NumPy oracle round rate on this problem (CoCoA+,
    additive), measured over a few rounds."""
    import oracle

    from cocoa_tpu.utils.prng import sample_indices

    X, y = ds_like
    sizes = np.full(k, X.shape[0] // k)
    sizes[: X.shape[0] % k] += 1
    offs = np.concatenate([[0], np.cumsum(sizes)])
    shards = [
        (X[offs[i]:offs[i + 1]], y[offs[i]:offs[i + 1]]) for i in range(k)
    ]
    w = np.zeros(X.shape[1])
    alphas = [np.zeros(Xk.shape[0]) for Xk, _ in shards]

    def step(t):
        nonlocal w
        dw_sum = np.zeros_like(w)
        for s, (Xk, yk) in enumerate(shards):
            idxs = sample_indices(0, range(t, t + 1), h, Xk.shape[0])[0]
            da, dw = oracle.local_sdca(
                Xk, yk, w, alphas[s], idxs, lam, n, True, float(k)
            )
            alphas[s] += da
            dw_sum += dw
        w += dw_sum

    return _round_rate(step, rounds)


def _oracle_rounds_per_s_sgd(ds_like, lam, h, k, rounds=3, local=True):
    """Single-thread oracle round rate for the SGD family (SGD.scala):
    per round each shard runs H Pegasos-style steps (local) or sums raw
    subgradients (mini-batch); driver applies the scaling law."""
    import oracle

    from cocoa_tpu.utils.prng import sample_indices

    X, y = ds_like
    sizes = np.full(k, X.shape[0] // k)
    sizes[: X.shape[0] % k] += 1
    offs = np.concatenate([[0], np.cumsum(sizes)])
    shards = [
        (X[offs[i]:offs[i + 1]], y[offs[i]:offs[i + 1]]) for i in range(k)
    ]
    w = np.zeros(X.shape[1])

    def step(t):
        nonlocal w
        if not local:
            eta = 1.0 / (lam * t)
            w = w * (1.0 - eta * lam)
        dw_sum = np.zeros_like(w)
        for sidx, (Xk, yk) in enumerate(shards):
            idxs = sample_indices(0, range(t, t + 1), h, Xk.shape[0])[0]
            t_global = (t - 1) * h * k
            dw_sum += oracle.sgd_partition(Xk, yk, w, idxs, lam, t_global,
                                           local)
        if local:
            w = w + dw_sum / k           # beta/K, beta=1 (SGD.scala:36,55)
        else:
            w = w + dw_sum * (eta / (k * h))   # eta*beta/(K*H) (:38,57-59)

    return _round_rate(step, rounds)


def _oracle_rounds_per_s_distgd(ds_like, lam, k, rounds=2):
    """Single-thread oracle round rate for DistGD (DistGD.scala): one
    deterministic full pass per shard per round + the normalized step."""
    import oracle

    X, y = ds_like
    sizes = np.full(k, X.shape[0] // k)
    sizes[: X.shape[0] % k] += 1
    offs = np.concatenate([[0], np.cumsum(sizes)])
    shards = [
        (X[offs[i]:offs[i + 1]], y[offs[i]:offs[i + 1]]) for i in range(k)
    ]
    w = np.zeros(X.shape[1])

    def step(t):
        nonlocal w
        dw = np.zeros_like(w)
        for Xk, yk in shards:
            dw += oracle.dist_gd_partition(Xk, yk, w, lam)
        nrm = np.linalg.norm(dw)
        if nrm > 0:
            w = w + dw * ((1.0 / t) / nrm)    # eta = 1/(beta*t), beta=1

    return _round_rate(step, rounds)


def bench_demo(results, perf_rows):
    import jax.numpy as jnp

    from cocoa_tpu.config import DebugParams, Params
    from cocoa_tpu.data import load_libsvm, shard_dataset
    from cocoa_tpu.solvers import run_cocoa

    data = load_libsvm(DEMO_TRAIN, DEMO_D)
    ds = shard_dataset(data, k=4, layout="dense", dtype=jnp.float32)
    debug = DebugParams(debug_iter=10, seed=0)

    def make_run(nr, rng="reference"):
        p = Params(n=data.n, num_rounds=nr, local_iters=50, lam=1e-3)
        return lambda: run_cocoa(ds, p, debug, plus=True, quiet=True,
                                 math="fast", device_loop=True, rng=rng)

    def gap_run(rng="reference"):
        p = Params(n=data.n, num_rounds=600, local_iters=50, lam=1e-3)
        return run_cocoa(ds, p, debug, plus=True, quiet=True, math="fast",
                         device_loop=True, gap_target=1e-4, rng=rng)

    w, a, traj = gap_run()
    rec = traj.records[-1]
    # the demo workload is tiny (~0.03 ms/round after the round-4 kernels);
    # the default escalation cap cannot build a jitter-dominating span, so
    # raise it for the demo rows rather than record them as noisy
    secs, fixed, q = _timed(make_run, rec.round, max_mult=256)
    rate = _oracle_rounds_per_s(
        (data.to_dense(), data.labels), 1e-3, 50, 4, data.n
    )
    results.append(dict(
        config="demo-cocoa+", n=data.n, d=DEMO_D, k=4, h=50,
        lam=1e-3, gap_target=1e-4, rounds=rec.round, gap=float(rec.gap),
        wallclock_s=round(secs, 3), fixed_s=round(fixed, 3), **q,
        vs_oracle=round(rec.round / rate / secs, 1),
        oracle_basis="measured (3 rounds)",
    ))
    perf_rows.append(_perf("demo-cocoa+", secs, rec.round, n=data.n,
                           d=DEMO_D, k=4, h=50, path="pallas"))

    # random reshuffling (--rng=permuted): fewer comm-rounds to the same
    # certified gap — the certificate is exact under any index stream
    w_p, a_p, traj_p = gap_run("permuted")
    rec_p = traj_p.records[-1]
    secs_p, fixed_p, q_p = _timed(
        lambda nr: make_run(nr, "permuted"), rec_p.round, max_mult=256)
    results.append(dict(
        config="demo-cocoa+(permuted)", n=data.n, d=DEMO_D, k=4, h=50,
        lam=1e-3, gap_target=1e-4, rounds=rec_p.round,
        gap=float(rec_p.gap), wallclock_s=round(secs_p, 3),
        fixed_s=round(fixed_p, 3), **q_p,
        vs_oracle_same_gap=round(rec.round / rate / secs_p, 1),
        oracle_basis="same-gap: oracle at reference-mode rounds",
    ))


def bench_epsilon(results, perf_rows, quick, data_dir=""):
    import jax.numpy as jnp

    from cocoa_tpu.config import DebugParams, Params
    from cocoa_tpu.data.synth import synth_dense_sharded
    from cocoa_tpu.solvers import run_cocoa, run_dist_gd, run_sgd

    real = None if quick else _maybe_real(data_dir, "epsilon_normalized")
    tag = "epsilon(real)" if real is not None else "epsilon"
    if real is not None:
        from cocoa_tpu.data import shard_dataset as _shard

        import jax.numpy as _jnp

        n, d, k = real.n, real.num_features, 8
        ds = _shard(real, k=k, layout="dense", dtype=_jnp.float32)
    else:
        n, d, k = (40_000, 2000, 8) if quick else (400_000, 2000, 8)
        ds = synth_dense_sharded(n, d, k, seed=0)
    h = n // k // 10
    debug = DebugParams(debug_iter=10, seed=0)

    def make_run(nr, rng="reference", block=0):
        p = Params(n=n, num_rounds=nr, local_iters=h, lam=1e-3)
        return lambda: run_cocoa(ds, p, debug, plus=True, quiet=True,
                                 math="fast", device_loop=True, rng=rng,
                                 block_size=block)

    def gap_run(rng="reference", block=0):
        p = Params(n=n, num_rounds=400, local_iters=h, lam=1e-3)
        return run_cocoa(ds, p, debug, plus=True, quiet=True, math="fast",
                         device_loop=True, gap_target=1e-4, rng=rng,
                         block_size=block)

    w, a, traj = gap_run()
    rec = traj.records[-1]
    secs, fixed, q = _timed(make_run, rec.round)
    # oracle rate on a small same-d subsample, scaled by n (per-round work
    # is O(H·d) per shard with H ∝ n — linear in n at fixed d, k)
    n_sub = min(n, 20_000)
    if real is not None:
        Xs, ys = _dense_subsample(real, n_sub)
    else:
        rng = np.random.default_rng(0)
        Xs = rng.standard_normal((n_sub, d))
        Xs /= np.linalg.norm(Xs, axis=1, keepdims=True)
        ys = np.where(Xs @ rng.standard_normal(d) >= 0, 1.0, -1.0)
    rate_sub = _oracle_rounds_per_s((Xs, ys), 1e-3, n_sub // k // 10, k, n_sub)
    rate = rate_sub * n_sub / n
    basis = f"extrapolated from n={n_sub} subsample"
    results.append(dict(
        config=f"{tag}-cocoa+", n=n, d=d, k=k, h=h,
        lam=1e-3, gap_target=1e-4, rounds=rec.round, gap=float(rec.gap),
        wallclock_s=round(secs, 3), fixed_s=round(fixed, 3), **q,
        vs_oracle=round(rec.round / rate / secs, 1), oracle_basis=basis,
    ))
    perf_rows.append(_perf(f"{tag}-cocoa+", secs, rec.round, n=n, d=d,
                           k=k, h=h, path="pallas"))

    # the block-coordinate inner solver (--blockSize=128): same index
    # stream and math, restructured for the MXU — the fused per-block
    # kernel (ops/pallas_chain.fused_block)
    w_b, a_b, traj_b = gap_run(block=128)
    rec_b = traj_b.records[-1]
    secs_b, fixed_b, q_b = _timed(lambda nr: make_run(nr, block=128),
                                  rec_b.round)
    results.append(dict(
        config=f"{tag}-cocoa+(block128)", n=n, d=d, k=k, h=h,
        lam=1e-3, gap_target=1e-4, rounds=rec_b.round,
        gap=float(rec_b.gap), wallclock_s=round(secs_b, 3),
        fixed_s=round(fixed_b, 3), **q_b,
        vs_oracle=round(rec_b.round / rate / secs_b, 1), oracle_basis=basis,
    ))
    perf_rows.append(_perf(f"{tag}-cocoa+(block128)", secs_b, rec_b.round,
                           n=n, d=d, k=k, h=h, path="block", block=128))

    # reshuffled sampling + block kernel: the TPU-first mode — same
    # certified 1e-4 gap in ~5x fewer comm-rounds (see tests/test_permuted)
    w_pb, a_pb, traj_pb = gap_run("permuted", block=128)
    rec_pb = traj_pb.records[-1]
    secs_pb, fixed_pb, q_pb = _timed(
        lambda nr: make_run(nr, "permuted", block=128), rec_pb.round)
    results.append(dict(
        config=f"{tag}-cocoa+(permuted+block128)", n=n, d=d, k=k, h=h,
        lam=1e-3, gap_target=1e-4, rounds=rec_pb.round,
        gap=float(rec_pb.gap), wallclock_s=round(secs_pb, 3),
        fixed_s=round(fixed_pb, 3), **q_pb,
        vs_oracle_same_gap=round(rec.round / rate / secs_pb, 1),
        oracle_basis="same-gap: oracle at reference-mode rounds",
    ))
    # the permuted+distinct block round in the ACCOUNTING table too
    # (VERDICT r5 weak #3: the measured distinct-path ms/round appeared
    # in no citable perf row — only the wall-clock table)
    perf_rows.append(_perf(f"{tag}-cocoa+(permuted+block128)", secs_pb,
                           rec_pb.round, n=n, d=d, k=k, h=h, path="block",
                           block=128))

    # Local SGD on the same data (primal-only baseline; fixed 100 rounds)
    d2 = DebugParams(debug_iter=100, seed=0)

    def make_sgd(nr, local=True):
        p = Params(n=n, num_rounds=nr, local_iters=h, lam=1e-3)
        return lambda: run_sgd(ds, p, d2, local=local, quiet=True,
                               device_loop=True)

    w2, traj2 = make_sgd(100)()
    rec2 = traj2.records[-1]
    secs2, fixed2, q2 = _timed(make_sgd, 100)
    rate_lsgd = _oracle_rounds_per_s_sgd((Xs, ys), 1e-3, n_sub // k // 10,
                                         k, local=True) * n_sub / n
    results.append(dict(
        config=f"{tag}-localsgd", n=n, d=d, k=k, h=h, lam=1e-3,
        rounds=rec2.round, primal=float(rec2.primal),
        wallclock_s=round(secs2, 3), fixed_s=round(fixed2, 3), **q2,
        vs_oracle=round(100 / rate_lsgd / secs2, 1), oracle_basis=basis,
    ))
    # SGD.scala:117-129 per step: O(d) rescale + conditional axpy — the
    # "exact"-path model (4·d per step, no margins pass) is the right count
    perf_rows.append(_perf(f"{tag}-localsgd", secs2, rec2.round, n=n, d=d,
                           k=k, h=h, path="exact", debug_iter=100))

    # Mini-batch SGD (SGD.scala local=false; fixed 100 rounds)
    w3, traj3 = make_sgd(100, local=False)()
    rec3 = traj3.records[-1]
    secs3, fixed3, q3 = _timed(lambda nr: make_sgd(nr, local=False), 100)
    rate_mbsgd = _oracle_rounds_per_s_sgd((Xs, ys), 1e-3, n_sub // k // 10,
                                          k, local=False) * n_sub / n
    results.append(dict(
        config=f"{tag}-mbsgd", n=n, d=d, k=k, h=h, lam=1e-3,
        rounds=rec3.round, primal=float(rec3.primal),
        wallclock_s=round(secs3, 3), fixed_s=round(fixed3, 3), **q3,
        vs_oracle=round(100 / rate_mbsgd / secs3, 1), oracle_basis=basis,
    ))
    perf_rows.append(_perf(f"{tag}-mbsgd", secs3, rec3.round, n=n, d=d,
                           k=k, h=h, path="exact", debug_iter=100))

    # DistGD (full deterministic subgradient pass per round; fixed 50
    # rounds — its per-round cost is a whole-shard pass, H-independent)
    from cocoa_tpu.config import Params as _P

    d3 = DebugParams(debug_iter=50, seed=0)

    def make_dgd(nr):
        p = _P(n=n, num_rounds=nr, local_iters=h, lam=1e-3)
        return lambda: run_dist_gd(ds, p, d3, quiet=True, device_loop=True)

    w4, traj4 = make_dgd(50)()
    rec4 = traj4.records[-1]
    secs4, fixed4, q4 = _timed(make_dgd, 50)
    # per-round cost is one full shard pass: rate scales 1/n at fixed d, k
    rate_dgd = _oracle_rounds_per_s_distgd((Xs, ys), 1e-3, k) * n_sub / n
    results.append(dict(
        config=f"{tag}-distgd", n=n, d=d, k=k, h="n/K",
        lam=1e-3, rounds=rec4.round, primal=float(rec4.primal),
        wallclock_s=round(secs4, 3), fixed_s=round(fixed4, 3), **q4,
        vs_oracle=round(50 / rate_dgd / secs4, 1), oracle_basis=basis,
    ))
    # DistGD reads every row once per round: model it as one "margins
    # pass" with zero coordinate steps
    import perf as _perfmod

    model = _perfmod.sdca_round_model(n, d, k, 0, path="fast")
    perf_rows.append(_perfmod.account(
        f"{tag}-distgd", secs4 / max(1, rec4.round), model,
        steps=n,   # one subgradient evaluation per example per round
        evals_per_round=1.0 / 50,
        eval_fl=_perfmod.eval_flops(n, d),
    ))


def bench_rcv1(results, perf_rows, quick, data_dir=""):
    import jax.numpy as jnp

    from cocoa_tpu.config import DebugParams, Params
    from cocoa_tpu.data.sharding import shard_dataset
    from cocoa_tpu.data.synth import synth_sparse
    from cocoa_tpu.solvers import run_cocoa, run_minibatch_cd

    real = None if quick else _maybe_real(data_dir, "rcv1_train.binary")
    rtag = "rcv1(real)" if real is not None else "rcv1"
    if real is not None:
        data, (n, d, k) = real, (real.n, real.num_features, 8)
    else:
        n, d, k = (4000, 47236, 8) if quick else (20242, 47236, 8)
        data = synth_sparse(n, d, nnz_mean=75, seed=0)
    # eval_dense: the certificate's full margins pass rides the MXU
    # instead of the every-nonzero w-gather — production A/B at this
    # config: 9.42 -> 6.46 ms/round (the gather eval was 31% of the round)
    ds = shard_dataset(data, k=k, layout="sparse", dtype=jnp.float32,
                       eval_dense=True)
    h = n // k // 10
    debug = DebugParams(debug_iter=25, seed=0)
    nnz = len(data.values) / n
    rate_plus = _oracle_rounds_per_s_csr(data, 1e-4, h, k, n, mode="plus")

    def make_run(nr, rng="reference", sigma=None):
        p = Params(n=n, num_rounds=nr, local_iters=h, lam=1e-4, sigma=sigma)
        return lambda: run_cocoa(ds, p, debug, plus=True, quiet=True,
                                 math="fast", device_loop=True, rng=rng)

    for gap_target in (1e-3, 1e-4):
        def gap_run(rng="reference", sigma=None, gap_target=gap_target,
                    accel=None, theta=None):
            p = Params(n=n, num_rounds=1500, local_iters=h, lam=1e-4,
                       sigma=sigma)
            return run_cocoa(ds, p, debug, plus=True, quiet=True,
                             math="fast", device_loop=True,
                             gap_target=gap_target, rng=rng, accel=accel,
                             theta=theta)

        w, a, traj = gap_run()
        rec = traj.records[-1]
        secs, fixed, q = _timed(make_run, rec.round)
        results.append(dict(
            config=f"{rtag}-cocoa+({gap_target:g})", n=n, d=d, k=k, h=h,
            lam=1e-4, gap_target=gap_target, rounds=rec.round,
            gap=float(rec.gap), wallclock_s=round(secs, 3),
            fixed_s=round(fixed, 3), **q,
            vs_oracle=round(rec.round / rate_plus / secs, 1),
            oracle_basis="measured (2 rounds, CSR)",
        ))
        perf_rows.append(_perf(f"{rtag}-cocoa+({gap_target:g})", secs,
                               rec.round, n=n, d=d, k=k, h=h,
                               layout="sparse", nnz=nnz, path="pallas",
                               debug_iter=25))

        w_p, a_p, traj_p = gap_run("permuted")
        rec_p = traj_p.records[-1]
        secs_p, fixed_p, q_p = _timed(
            lambda nr: make_run(nr, "permuted"), rec_p.round)
        results.append(dict(
            config=f"{rtag}-cocoa+({gap_target:g}, permuted)", n=n, d=d,
            k=k, h=h, lam=1e-4, gap_target=gap_target,
            rounds=rec_p.round, gap=float(rec_p.gap),
            wallclock_s=round(secs_p, 3), fixed_s=round(fixed_p, 3), **q_p,
            vs_oracle_same_gap=round(rec.round / rate_plus / secs_p, 1),
            oracle_basis="same-gap: oracle at reference-mode rounds",
        ))

        if gap_target == 1e-4:
            # the comm-round attack (VERDICT r3 item 3): comm-rounds IS
            # the baseline metric, and at λ=1e-4 the safe σ′=K needs
            # ~1150 of them.  Every lever was measured: 10x local work
            # (localIterFrac=1) saturates at ~2.8x fewer rounds-to-7e-4
            # then stalls; γ<1 is strictly worse; a smooth-hinge warm
            # start moves nothing (±25 rounds); σ′ < K/2 diverges
            # (σ′=3.5 at K=8 — visibly, the certificate is exact).
            # σ′ = K/2 (--sigma) HALVES the certified rounds — the one
            # lever that pays, recorded as its own row.
            _, _, traj_s = gap_run("permuted", sigma=k / 2.0)
            rec_s = traj_s.records[-1]
            secs_s, fixed_s_, q_s = _timed(
                lambda nr: make_run(nr, "permuted", sigma=k / 2.0),
                rec_s.round)
            results.append(dict(
                config=f"{rtag}-cocoa+({gap_target:g}, permuted, "
                       f"sigma=K/2)",
                n=n, d=d, k=k, h=h, lam=1e-4, gap_target=gap_target,
                rounds=rec_s.round, gap=float(rec_s.gap),
                wallclock_s=round(secs_s, 3), fixed_s=round(fixed_s_, 3),
                **q_s,
                vs_oracle_same_gap=round(
                    rec.round / rate_plus / secs_s, 1),
                oracle_basis="same-gap: oracle at reference-mode rounds",
            ))

            # the HEADLINED rcv1 production row (VERDICT r5 next #2):
            # permuted sampling + σ′=auto (the guarded K·γ/2 trial with
            # the safe fallback) + the dense eval twin (ds above is built
            # with eval_dense=True) — the config the production CLI flags
            # select, stated in the table next to the reference-faithful
            # rows whose parallel-oracle column reads sub-parity.
            _, _, traj_pr = gap_run("permuted", sigma="auto")
            rec_pr = traj_pr.records[-1]
            # time fixed-round runs at the σ′ the auto procedure settled
            # on.  sigma=auto rides the in-loop anneal schedule now
            # (--sigmaSchedule=anneal, the default): it starts at K·γ/2
            # and backs off in place only if the stall watch fires.  On
            # this config the aggressive start holds (the explicit K·γ/2
            # row above certifies — same seed, same config), so the
            # anneal run is bit-identical to fixed σ′=K/2 and that is
            # the right σ′ for the timing runs; were the K·γ/2 row
            # diverging, auto would have annealed toward safe K·γ.
            sig_used = None if traj_s.stopped == "diverged" else k / 2.0
            secs_pr, fixed_pr, q_pr = _timed(
                lambda nr: make_run(nr, "permuted", sigma=sig_used),
                rec_pr.round)
            results.append(dict(
                config=f"{rtag}-cocoa+(production: permuted+sigma=auto"
                       f"+evalDense)",
                n=n, d=d, k=k, h=h, lam=1e-4, gap_target=gap_target,
                rounds=rec_pr.round, gap=float(rec_pr.gap),
                wallclock_s=round(secs_pr, 3), fixed_s=round(fixed_pr, 3),
                **q_pr,
                vs_oracle_same_gap=round(
                    rec.round / rate_plus / secs_pr, 1),
                oracle_basis="same-gap: oracle at reference-mode rounds",
            ))
            perf_rows.append(_perf(
                f"{rtag}-cocoa+(production)", secs_pr, rec_pr.round,
                n=n, d=d, k=k, h=h, layout="sparse", nnz=nnz,
                path="pallas", debug_iter=25))

            # rounds-to-gap A/B for the accelerated outer loop (round
            # 12): --accel=on --theta=adaptive against the production
            # row above as the --accel=off control — identical data,
            # sampler, σ′ policy, gap target and the UNMODIFIED gap
            # evaluator; the only change is the momentum/Θ machinery.
            # `rounds` is the headline column (in the distributed regime
            # comm-rounds ARE the cost, so the ratio multiplies every
            # per-round win already recorded).  `accel_floor_rounds` is
            # the theoretical Nesterov floor from the control's own
            # contraction rate (perf.predict_accel_rounds) — measured
            # sits between the control and it.
            import perf

            def accel_floor(rounds_plain, traj_ctrl):
                # the control's first logged gap can be NaN (a transient
                # divergence at an aggressive σ′ start) or already past
                # the target — either would make predict_accel_rounds
                # raise and lose the sweep's accumulated rows, so the
                # floor cell degrades to None instead
                g0 = (float(traj_ctrl.records[0].gap)
                      if traj_ctrl.records and traj_ctrl.records[0].gap
                      else 1.0)
                if not (np.isfinite(g0) and g0 > gap_target):
                    return None
                return perf.predict_accel_rounds(rounds_plain, g0,
                                                 gap_target)

            _, _, traj_ac = gap_run("permuted", sigma="auto", accel="on",
                                    theta="adaptive")
            rec_ac = traj_ac.records[-1]
            results.append(dict(
                config=f"{rtag}-cocoa+(accel: on+theta=adaptive)",
                n=n, d=d, k=k, h=h, lam=1e-4, gap_target=gap_target,
                rounds=rec_ac.round, gap=float(rec_ac.gap),
                stopped=traj_ac.stopped,
                control_rounds=rec_pr.round,
                rounds_ratio=round(rec_pr.round / max(1, rec_ac.round), 2),
                accel_floor_rounds=accel_floor(rec_pr.round, traj_pr),
                oracle_basis="comm-rounds A/B vs the production row "
                             "(accel=off control, same gap target)",
            ))

            # the same A/B at the reference's safe σ′ = K·γ (the
            # `(0.0001, permuted)` row above as control) — the
            # worse-conditioned regime where acceleration pays the most
            # (measured 1.76× vs the production point's 1.38×; the
            # κ→√κ floor says the ratio must grow with control rounds)
            _, _, traj_as = gap_run("permuted", accel="on")
            rec_as = traj_as.records[-1]
            results.append(dict(
                config=f"{rtag}-cocoa+({gap_target:g}, permuted, "
                       f"accel=on)",
                n=n, d=d, k=k, h=h, lam=1e-4, gap_target=gap_target,
                rounds=rec_as.round, gap=float(rec_as.gap),
                stopped=traj_as.stopped,
                control_rounds=rec_p.round,
                rounds_ratio=round(rec_p.round / max(1, rec_as.round), 2),
                accel_floor_rounds=accel_floor(rec_p.round, traj_p),
                oracle_basis="comm-rounds A/B vs the permuted safe-σ′ "
                             "row (accel=off control, same gap target)",
            ))

        if gap_target == 1e-3:
            # the in-loop σ′ backoff demonstration (round 8): start the
            # anneal schedule at a deliberately divergence-prone σ′ =
            # K·γ/8 = 1 (anything below K/2 diverges on this data — the
            # sweep above) and let the device-resident controller back
            # off toward safe K·γ inside the while_loop.  The row's
            # `rounds` is the WHOLE story: detection window + in-place
            # recovery, zero restarts, versus the trial-style
            # window + full restart + rerun (benchmarks/SWEEPS.md
            # "anneal vs trial").  1e-3 target keeps the recovery tail
            # out of the λ=1e-4 conditioning regime.
            p_an = Params(n=n, num_rounds=1600, local_iters=h, lam=1e-4,
                          sigma=1.0)
            _, _, traj_an = run_cocoa(
                ds, p_an, debug, plus=True, quiet=True, math="fast",
                device_loop=True, gap_target=gap_target, rng="permuted",
                sigma_schedule="anneal")
            rec_an = traj_an.records[-1]
            sig_path = sorted({r.sigma for r in traj_an.records
                               if r.sigma is not None})
            results.append(dict(
                config=f"{rtag}-cocoa+({gap_target:g}, permuted, "
                       f"anneal from sigma'=1)",
                n=n, d=d, k=k, h=h, lam=1e-4, gap_target=gap_target,
                rounds=rec_an.round, gap=float(rec_an.gap),
                stopped=traj_an.stopped,
                sigma_ladder="->".join(f"{s:g}" for s in sig_path),
                oracle_basis="comm-rounds only (in-loop backoff demo; "
                             "wall-clock tracks the fixed-σ′ rows)",
            ))

    # Mini-batch CD on the same data (fixed 100 rounds; its β/(K·H)
    # scaling needs far more rounds per unit of gap progress — the CoCoA
    # papers' point)
    d2 = DebugParams(debug_iter=100, seed=0)

    def make_mbcd(nr):
        p = Params(n=n, num_rounds=nr, local_iters=h, lam=1e-4)
        return lambda: run_minibatch_cd(ds, p, d2, quiet=True, math="fast",
                                        device_loop=True)

    w2, a2, traj2 = make_mbcd(100)()
    rec2 = traj2.records[-1]
    secs2, fixed2, q2 = _timed(make_mbcd, 100)
    rate_f = _oracle_rounds_per_s_csr(data, 1e-4, h, k, n, mode="frozen")
    results.append(dict(
        config=f"{rtag}-mbcd", n=n, d=d, k=k, h=h, lam=1e-4,
        rounds=rec2.round, gap=float(rec2.gap), primal=float(rec2.primal),
        wallclock_s=round(secs2, 3), fixed_s=round(fixed2, 3), **q2,
        vs_oracle=round(rec2.round / rate_f / secs2, 1),
        oracle_basis="measured (2 rounds, CSR)",
    ))
    perf_rows.append(_perf(f"{rtag}-mbcd", secs2, rec2.round, n=n, d=d, k=k,
                           h=h, layout="sparse", nnz=nnz, path="pallas",
                           debug_iter=100))

def _np_alpha_step(loss, a, z, qii, lam_n, smoothing):
    """NumPy twin of ops/losses.alpha_step (scalar), for the loss-variant
    oracle rates."""
    if loss == "smooth_hinge":
        s = smoothing
        grad = (z - 1.0 + s * a) * lam_n
        return min(max(a - grad / (qii + s * lam_n), 0.0), 1.0)
    if loss == "logistic":
        ac = min(max(a, 1e-12), 1.0 - 1e-12)
        q = qii / lam_n
        u = min(max(np.log(ac / (1.0 - ac)), -35.0), 35.0)
        for _ in range(10):
            sig = 1.0 / (1.0 + np.exp(-u))
            g = u + z + q * (sig - ac)
            gp = 1.0 + q * sig * (1.0 - sig)
            u = min(max(u - g / gp, -35.0), 35.0)
        return 1.0 / (1.0 + np.exp(-u))
    raise ValueError(loss)


def _oracle_rounds_per_s_loss(ds_like, lam, h, k, n, loss, smoothing,
                              rounds=3):
    """Single-thread oracle round rate for the non-hinge dual-ascent
    losses (CoCoA+ additive): the same per-step structure as
    oracle.local_sdca with the loss's coordinate update."""
    from cocoa_tpu.utils.prng import sample_indices

    X, y = ds_like
    sizes = np.full(k, X.shape[0] // k)
    sizes[: X.shape[0] % k] += 1
    offs = np.concatenate([[0], np.cumsum(sizes)])
    shards = [
        (X[offs[i]:offs[i + 1]], y[offs[i]:offs[i + 1]]) for i in range(k)
    ]
    w = np.zeros(X.shape[1])
    alphas = [np.zeros(Xk.shape[0]) for Xk, _ in shards]
    sigma = float(k)
    lam_n = lam * n

    def step(t):
        nonlocal w
        dw_sum = np.zeros_like(w)
        for s, (Xk, yk) in enumerate(shards):
            idxs = sample_indices(0, range(t, t + 1), h, Xk.shape[0])[0]
            a = alphas[s]
            dw = np.zeros_like(w)
            for li in idxs:
                x = Xk[li]
                z = yk[li] * (x @ w + sigma * (x @ dw))
                qii = sigma * float(x @ x)
                new_a = _np_alpha_step(loss, a[li], z, qii, lam_n, smoothing)
                coef = yk[li] * (new_a - a[li]) / lam_n
                dw += coef * x
                a[li] = new_a
            dw_sum += dw
        w = w + dw_sum

    return _round_rate(step, rounds)


def bench_losses(results, perf_rows, quick):
    """The fifth BASELINE.json config (VERDICT r3 item 2): the
    smoothed-hinge and logistic local-solver variants — the reference's
    explicit extensibility promise (README.md:14, CoCoA.scala:13-14) —
    measured gap-targeted at epsilon scale through the fused block kernel,
    exercising the non-hinge chain (smooth-hinge's shifted clip, the
    10-iteration unrolled Newton for logistic) at scale."""
    from cocoa_tpu.config import DebugParams, Params
    from cocoa_tpu.data.synth import synth_dense_sharded
    from cocoa_tpu.solvers import run_cocoa

    n, d, k = (40_000, 2000, 8) if quick else (400_000, 2000, 8)
    ds = synth_dense_sharded(n, d, k, seed=0)
    h = n // k // 10
    debug = DebugParams(debug_iter=10, seed=0)
    n_sub = min(n, 20_000)
    rng = np.random.default_rng(0)
    Xs = rng.standard_normal((n_sub, d))
    Xs /= np.linalg.norm(Xs, axis=1, keepdims=True)
    ys = np.where(Xs @ rng.standard_normal(d) >= 0, 1.0, -1.0)

    for loss, smoothing, gap_target in (
        ("smooth_hinge", 1.0, 1e-4),
        ("logistic", 1.0, 1e-4),
    ):
        def make_run(nr, loss=loss, smoothing=smoothing):
            p = Params(n=n, num_rounds=nr, local_iters=h, lam=1e-3,
                       loss=loss, smoothing=smoothing)
            return lambda: run_cocoa(ds, p, debug, plus=True, quiet=True,
                                     math="fast", device_loop=True,
                                     block_size=128)

        p = Params(n=n, num_rounds=600, local_iters=h, lam=1e-3,
                   loss=loss, smoothing=smoothing)
        w, a, traj = run_cocoa(ds, p, debug, plus=True, quiet=True,
                               math="fast", device_loop=True,
                               gap_target=gap_target, block_size=128)
        rec = traj.records[-1]
        if rec.gap is None or rec.gap > gap_target:
            # record honestly as a budget-capped row, never as a
            # gap-certified one
            q_miss = {"gap_miss": True}
        else:
            q_miss = {}
        secs, fixed, q = _timed(make_run, rec.round)
        q = {**q, **q_miss}
        rate = _oracle_rounds_per_s_loss(
            (Xs, ys), 1e-3, n_sub // k // 10, k, n_sub, loss, smoothing
        ) * n_sub / n
        results.append(dict(
            config=f"epsilon-{loss}(block128)", n=n, d=d, k=k, h=h,
            lam=1e-3, gap_target=gap_target, rounds=rec.round,
            gap=None if rec.gap is None else float(rec.gap),
            wallclock_s=round(secs, 3),
            fixed_s=round(fixed, 3), **q,
            vs_oracle=round(rec.round / rate / secs, 1),
            oracle_basis=f"extrapolated from n={n_sub} subsample",
        ))
        perf_rows.append(_perf(f"epsilon-{loss}(block128)", secs, rec.round,
                               n=n, d=d, k=k, h=h, path="block", block=128))


def _oracle_rounds_per_s_lasso(A, bvec, lam, h, k, rounds=2, l2=0.0):
    """Single-thread literal prox-CD oracle round rate (ProxCoCoA+ lasso /
    elastic net, gamma=1): per step one column dot against r, one against
    the local Δv, a soft-threshold, one column axpy."""
    from cocoa_tpu.data.sharding import split_sizes
    from cocoa_tpu.utils.prng import sample_indices

    n, d = A.shape
    A = np.asfortranarray(A)  # contiguous columns — the unit of access,
                              # as Breeze column vectors are materialized
    sizes = split_sizes(d, k)
    offs = np.concatenate([[0], np.cumsum(sizes)])
    sigma = float(k)
    # jaxlint: allow=f64 -- the pinned CPU oracle is Breeze-faithful f64
    # by definition; it is what the f32 TPU runs are measured against
    r = -bvec.astype(np.float64)
    x = np.zeros(d)

    def step(t):
        nonlocal r
        dv_sum = np.zeros(n)
        for sh in range(k):
            idxs = sample_indices(0, range(t, t + 1), h, sizes[sh])[0]
            dv = np.zeros(n)
            for lj in idxs:
                gj = offs[sh] + lj
                aj = A[:, gj]
                a = x[gj]
                z = aj @ r + sigma * (aj @ dv)
                q = sigma * float(aj @ aj)
                if q <= 0.0:
                    continue
                u = (q * a - z) / (q + l2)
                tstar = np.sign(u) * max(abs(u) - lam / (q + l2), 0.0)
                dv += aj * (tstar - a)
                x[gj] = tstar
            dv_sum += dv
        r = r + dv_sum

    return _round_rate(step, rounds)


def bench_lasso(results, perf_rows, quick):
    """ProxCoCoA+ lasso + elastic net (the L1 extension, no reference
    analogue): dense Gaussian design with a planted 64-sparse x*,
    λ = 0.3·λ_max, to a RELATIVE duality gap of 1e-3 (gap ≤ 1e-3·½‖b‖² —
    these objectives are scale-dependent, so an absolute target would be
    meaningless).  The elastic-net row exercises the smoothed-conjugate
    certificate (VERDICT r2 item 4)."""
    import jax.numpy as jnp

    from cocoa_tpu.config import DebugParams, Params
    from cocoa_tpu.data.columns import shard_columns
    from cocoa_tpu.data.libsvm import LibsvmData
    from cocoa_tpu.solvers import run_prox_cocoa

    n, d, k = (2048, 8192, 8) if quick else (8192, 32768, 8)
    rng = np.random.default_rng(0)
    A = rng.standard_normal((n, d)).astype(np.float32) / np.sqrt(n)
    x_true = np.zeros(d, np.float32)
    x_true[rng.choice(d, 64, replace=False)] = \
        rng.standard_normal(64).astype(np.float32) * 3
    bvec = A @ x_true + 0.01 * rng.standard_normal(n).astype(np.float32)
    indptr = np.arange(0, (n + 1) * d, d, dtype=np.int64)
    # values stay f32: shard_columns casts to the compute dtype anyway, and
    # an f64 copy of the dense design would be a ~2 GB host transient
    # jaxlint: allow=f64 -- LibsvmData labels ride the container's f64
    # host contract (cast at shard time)
    data = LibsvmData(labels=bvec.astype(np.float64), indptr=indptr,
                      indices=np.tile(np.arange(d, dtype=np.int32), n),
                      values=A.reshape(-1), num_features=d)
    ds, b = shard_columns(data, k, dtype=jnp.float32)
    lam = 0.3 * float(np.max(np.abs(A.T @ bvec)))
    p0 = 0.5 * float(bvec @ bvec)
    h = d // k // 10
    debug = DebugParams(debug_iter=50, seed=0)

    for tag, l2 in (("lasso-proxcocoa+", 0.0), ("elastic-proxcocoa+", 0.1)):
        def make_run(nr, rng_mode="reference", l2=l2):
            p = Params(n=d, num_rounds=nr, local_iters=h, lam=lam,
                       loss="lasso", smoothing=l2)
            return lambda: run_prox_cocoa(ds, b, p, debug, quiet=True,
                                          math="fast", device_loop=True,
                                          rng=rng_mode)

        def gap_run(rng_mode="reference", l2=l2):
            p = Params(n=d, num_rounds=3000, local_iters=h, lam=lam,
                       loss="lasso", smoothing=l2)
            return run_prox_cocoa(ds, b, p, debug, quiet=True, math="fast",
                                  device_loop=True, gap_target=1e-3 * p0,
                                  rng=rng_mode)

        x, r, traj = gap_run()
        rec = traj.records[-1]
        secs, fixed, q = _timed(make_run, rec.round)
        rate = _oracle_rounds_per_s_lasso(A, bvec, lam, h, k, l2=l2)
        results.append(dict(
            config=tag, n=n, d=d, k=k, h=h,
            lam=round(lam, 5), l2=l2, gap_target="1e-3 relative",
            rounds=rec.round, gap=float(rec.gap),
            wallclock_s=round(secs, 3), fixed_s=round(fixed, 3), **q,
            vs_oracle=round(rec.round / rate / secs, 1),
            oracle_basis="measured (2 rounds)",
        ))
        # roles swapped: d coordinates play the example axis, dense columns
        # of length n play the rows (see solvers/prox_cocoa.py)
        perf_rows.append(_perf(tag, secs, rec.round, n=d, d=n,
                               k=k, h=h, path="pallas", debug_iter=50))

        if l2 == 0.0:
            x_p, r_p, traj_p = gap_run("permuted")
            rec_p = traj_p.records[-1]
            secs_p, fixed_p, q_p = _timed(
                lambda nr: make_run(nr, "permuted"), rec_p.round)
            results.append(dict(
                config="lasso-proxcocoa+(permuted)", n=n, d=d, k=k, h=h,
                lam=round(lam, 5), gap_target="1e-3 relative",
                rounds=rec_p.round, gap=float(rec_p.gap),
                wallclock_s=round(secs_p, 3), fixed_s=round(fixed_p, 3), **q_p,
                vs_oracle_same_gap=round(rec.round / rate / secs_p, 1),
                oracle_basis="same-gap: oracle at reference-mode rounds",
            ))


# Each ingest bench worker is a PLAIN subprocess (no jax import): its
# ru_maxrss then reflects the parse artifacts — the cost the A/B is
# about — not the ~350 MB backend baseline.  Device placement is
# identical in both modes (HBM on a real TPU, excluded here); the worker
# replays exactly the per-process parse work of the two ingest paths
# over ranges the parent derives from the real pass-1 index.
_INGEST_WORKER = r"""
import importlib.util, json, os, resource, sys, time, types
spec = json.load(open(sys.argv[1]))
import numpy as np

# load the parser modules by FILE PATH, not through the package: the
# cocoa_tpu package __init__ imports jax, whose ~350 MB import peak
# would swallow the parse-artifact RSS this worker exists to measure
def _load(name, relpath):
    s = importlib.util.spec_from_file_location(
        name, os.path.join(spec["root"], relpath))
    m = importlib.util.module_from_spec(s)
    sys.modules[name] = m
    s.loader.exec_module(m)
    return m

sys.modules["cocoa_tpu"] = types.ModuleType("cocoa_tpu")
sys.modules["cocoa_tpu.data"] = types.ModuleType("cocoa_tpu.data")
_libsvm = _load("cocoa_tpu.data.libsvm", "cocoa_tpu/data/libsvm.py")
sys.modules["cocoa_tpu.data"].native_loader = _load(
    "cocoa_tpu.data.native_loader", "cocoa_tpu/data/native_loader.py")
sys.modules["cocoa_tpu.data"].libsvm = _libsvm
# slab_cache is deliberately numpy-only (no jax), so the warm mode loads
# it the same file-path way — its mmap'd artifacts ARE this worker's RSS
_slab_cache = _load("cocoa_tpu.data.slab_cache",
                    "cocoa_tpu/data/slab_cache.py")
load_libsvm, load_libsvm_range = _libsvm.load_libsvm, _libsvm.load_libsvm_range

def rss_kb():
    # current resident set from statm — ru_maxrss is unusable here (this
    # kernel carries the PARENT's high-water mark across fork+exec).
    # Sampled while the parse artifacts are live, so it reads the
    # held-CSR peak the A/B is about.
    pages = int(open("/proc/self/statm").read().split()[1])
    return pages * os.sysconf("SC_PAGE_SIZE") // 1024

path, d, mode = spec["path"], spec["d"], spec["mode"]
rss0 = rss_kb()
rss_peak = 0
t0 = time.perf_counter()
bytes_read = rows = nnz = 0
bytes_mapped = 0
if mode == "warm":
    # --ingestCache warm ingest (data/slab_cache.py): map + validate
    # this process's shards' device-ready slab artifacts — zero parse.
    # Device placement is excluded exactly as in the other modes (the
    # device_put cost is identical cold or warm).  bytes_read stays the
    # TEXT bytes parsed (0 by contract — the regression gate fails a
    # warm row that ever reads text); the mapped artifact bytes report
    # separately as bytes_mapped.
    cache = _slab_cache.SlabCache(spec["cache_dir"])
    handle = cache.for_file(path, d)
    view = handle.view(layout="sparse", k=spec["k"],
                       n_shard=spec["n_shard"], width=spec["width"],
                       n_hot=0, d=d, dtype=np.float32, eval_dense=False)
    for s in spec["shards"]:
        slab = view.load(s)
        assert slab is not None, f"warm bench: shard {s} missed"
        rows += int((slab["mask"] > 0).sum())
        rss_peak = max(rss_peak, rss_kb())
    bytes_mapped = cache.bytes_mapped
elif mode == "whole":
    # whole-file ingest: every process parses the entire file and holds
    # the full CSR before slicing out its shards (load_libsvm ->
    # _shard_dataset_distributed)
    data = load_libsvm(path, d)
    rss_peak = rss_kb()
    rows, nnz = data.n, int(data.indptr[-1])
    bytes_read = os.path.getsize(path)
else:
    # pass 1 (data/ingest.build_index): windowed range scan of this
    # process's 1/P — stats kept, rows dropped
    lo, hi = spec["scan_range"]
    hist = np.zeros(d, np.int64)
    nnz_parts = []
    w = lo
    while w < hi:
        piece, off = load_libsvm_range(path, d, w, min(w + spec["window"], hi))
        hist += np.bincount(piece.indices, minlength=d)
        nnz_parts.append(np.diff(piece.indptr))
        rss_peak = max(rss_peak, rss_kb())
        w = min(w + spec["window"], hi)
    bytes_read += hi - lo
    # pass 2 (stream_shard_dataset): parse ONLY this process's local
    # devices' shard byte ranges, held one device-piece at a time
    for blo, bhi in spec["piece_ranges"]:
        piece, _ = load_libsvm_range(path, d, blo, bhi)
        rss_peak = max(rss_peak, rss_kb())
        rows += piece.n
        nnz += len(piece.values)
        bytes_read += bhi - blo
secs = time.perf_counter() - t0
json.dump(dict(secs=secs, bytes_read=bytes_read,
               bytes_mapped=bytes_mapped, rows=rows, nnz=nnz,
               rss0_kb=rss0, rss1_kb=rss_peak),
          open(spec["out"], "w"))
"""


def bench_ingest(results, quick, processes=(2, 8)):
    """Streaming vs whole-file ingest A/B at rcv1-synth scale (the ISSUE 8
    acceptance row): per-PROCESS parse wallclock, bytes read, and peak
    host RSS for a P-process run, measured by replaying each process's
    exact parse work in a clean subprocess.

    ``whole``: every process parses the entire file and holds the full
    CSR.  ``stream`` (data/ingest.py): pass-1 range scan of 1/P of the
    file + pass-2 parse of only its own shards' byte ranges.  The
    wallclock win scales as ~P/2 (at P=2 the streamed path parses the
    same total bytes, split across passes); the RSS win is the point at
    P=2 already — the held CSR drops to ~1/P of the dataset plus the
    index (the ``rss_vs_whole`` column, acceptance bar ≤ ~0.6 at P=2).
    Model predictions from perf.ingest_model ride each row.

    ``warm`` (--ingestCache, data/slab_cache.py, the ISSUE 15 row): the
    parent primes the cache with one cold streamed build, then each
    process maps + validates ONLY its own shards' slab artifacts — zero
    parse.  Acceptance bar: ≥10× faster than the streamed cold parse of
    the same geometry (the ``warm_speedup`` column,
    check_regression-gated).  Device placement is excluded in every
    mode — it is identical cold or warm.
    """
    import subprocess
    import sys as _sys
    import tempfile

    import jax.numpy as jnp

    import perf
    from cocoa_tpu.data import SlabCache, stream_shard_dataset
    from cocoa_tpu.data.ingest import PASS1_WINDOW, build_index
    from cocoa_tpu.data.sharding import pad_rows, split_sizes
    from cocoa_tpu.data.synth import synth_sparse, write_libsvm

    n, d, nnz_mean, k = ((2024, 4724, 20, 8) if quick
                        else (20242, 47236, 75, 8))
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "rcv1_synth.svm")
        write_libsvm(synth_sparse(n, d, nnz_mean=nnz_mean, seed=0), path)
        fsize = os.path.getsize(path)
        index = build_index(path, d)
        sizes = split_sizes(index.n, k)
        offsets = np.concatenate([[0], np.cumsum(sizes)])
        # prime the slab cache once (a cold streamed build through the
        # real pipeline) so the warm rows measure EXACTLY what a second
        # process pays: map + validate, zero parse
        cache_dir = os.path.join(tmp, "icache")
        stream_shard_dataset(path, d, k, layout="sparse",
                             dtype=jnp.float32,
                             cache=SlabCache(cache_dir))
        n_shard = pad_rows(int(sizes.max()))
        width = int(max(1, index.row_nnz.max(initial=1)))

        def run_worker(spec):
            spec_path = spec["out"] + ".spec"
            json.dump(spec, open(spec_path, "w"))
            subprocess.run([_sys.executable, "-c", _INGEST_WORKER,
                            spec_path], check=True, cwd=tmp)
            return json.load(open(spec["out"]))

        for nproc in processes:
            if k % nproc:
                continue
            m = k // nproc  # shards multiplexed per process's device
            rows = {}
            for mode in ("whole", "stream", "warm"):
                reps = []
                for p in range(nproc):
                    r0, r1 = int(offsets[p * m]), int(offsets[(p + 1) * m])
                    reps.append(run_worker(dict(
                        root=ROOT, path=path, d=d, mode=mode,
                        window=PASS1_WINDOW,
                        scan_range=[p * fsize // nproc,
                                    (p + 1) * fsize // nproc],
                        piece_ranges=[[int(index.row_off[r0]),
                                       int(index.row_off[r1])]],
                        cache_dir=cache_dir, k=k, n_shard=n_shard,
                        width=width,
                        shards=list(range(p * m, (p + 1) * m)),
                        out=os.path.join(tmp, f"{mode}{nproc}_{p}.json"),
                    )))
                row = dict(
                    config=f"ingest/{mode}-p{nproc}"
                           + ("(quick)" if quick else ""),
                    n=index.n, d=d, k=k, mode=mode, processes=nproc,
                    file_mb=round(fsize / 2**20, 1),
                    parse_s=round(max(r["secs"] for r in reps), 4),
                    bytes_read_mb=round(
                        max(r["bytes_read"] for r in reps) / 2**20, 1),
                    peak_rss_mb=round(
                        max(r["rss1_kb"] for r in reps) / 1024, 1),
                    rss_delta_mb=round(
                        max(r["rss1_kb"] - r["rss0_kb"] for r in reps)
                        / 1024, 1),
                )
                if mode == "warm":
                    # bytes_read_mb is TEXT parsed on the warm path —
                    # 0.0 by contract, kept in the row so the
                    # check_regression gate can fail a warm mode that
                    # ever starts reading text; the mapped artifact
                    # bytes report separately
                    row["bytes_mapped_mb"] = round(
                        max(r["bytes_mapped"] for r in reps) / 2**20, 1)
                else:
                    pred = perf.ingest_model(fsize, index.n,
                                             index.total_nnz,
                                             nproc, mode=mode, d=d)
                    row["predicted_parse_s"] = round(
                        pred["parse_seconds"], 3)
                    row["predicted_csr_mb"] = round(
                        pred["csr_peak_bytes"] / 2**20, 1)
                rows[mode] = row
                results.append(row)
            ratio = (rows["stream"]["rss_delta_mb"]
                     / max(rows["whole"]["rss_delta_mb"], 1e-9))
            rows["stream"]["rss_vs_whole"] = round(ratio, 2)
            speedup = (rows["stream"]["parse_s"]
                       / max(rows["warm"]["parse_s"], 1e-9))
            rows["warm"]["warm_speedup"] = round(speedup, 1)
            print(f"bench: ingest p={nproc} — whole "
                  f"{rows['whole']['parse_s']}s/"
                  f"{rows['whole']['rss_delta_mb']}MB vs stream "
                  f"{rows['stream']['parse_s']}s/"
                  f"{rows['stream']['rss_delta_mb']}MB "
                  f"(rss ratio {ratio:.2f}, bar ≤0.6 at p=2) vs warm "
                  f"{rows['warm']['parse_s']}s "
                  f"({speedup:.0f}× stream, bar ≥10×)")


def write_results(results, perf_rows, out_dir, partial=False, final=False):
    """Full runs own results.jsonl / RESULTS.md (the artifacts BASELINE.md
    cites); --quick / --only runs write to *.partial.* so they can never
    clobber the recorded numbers.  Mid-suite flushes of a FULL run write
    to *.inprogress.* and only the ``final`` write owns the canonical
    files: a device lost mid-suite then leaves
    the recorded artifacts untouched while the sections already measured
    survive in the inprogress files.  The BASELINE.md/PARITY.md/README.md
    doc blocks likewise sync only on ``final``."""
    suffix = ".partial" if partial else ("" if final else ".inprogress")
    for r in results:
        # ideal-parallel-oracle columns (VERDICT r5 next #2): the
        # single-thread oracle ratio divided by the row's K — the speedup
        # against an IDEAL K-way-parallel CPU run of the reference math
        # (zero scheduling cost; real Spark sits below it, so the truth
        # lies between the two columns).  This is the honest denominator
        # for the ≥10x north star (BASELINE.json argues against an
        # 8-executor cluster, which can use at most K-way parallelism).
        kk = r.get("k")
        if kk:
            if (r.get("vs_oracle") is not None
                    and r.get("vs_oracle_parallel") is None):
                r["vs_oracle_parallel"] = round(r["vs_oracle"] / kk, 2)
            if (r.get("vs_oracle_same_gap") is not None
                    and r.get("vs_oracle_parallel_same_gap") is None):
                r["vs_oracle_parallel_same_gap"] = round(
                    r["vs_oracle_same_gap"] / kk, 2)
    jl = os.path.join(out_dir, f"results{suffix}.jsonl")
    with open(jl, "w") as f:
        for r in results:
            f.write(json.dumps(r) + "\n")
        for r in perf_rows:
            f.write(json.dumps({"type": "perf", **r}) + "\n")
    md = os.path.join(out_dir, f"RESULTS{suffix}.md")
    cols = ["config", "n", "d", "k", "h", "lam", "l2", "gap_target",
            "rounds", "gap", "primal", "wallclock_s", "fixed_s",
            "vs_oracle", "vs_oracle_parallel", "vs_oracle_same_gap",
            "vs_oracle_parallel_same_gap"]
    with open(md, "w") as f:
        f.write("# Benchmark results\n\n")
        f.write("Produced by `python benchmarks/run.py` on the attached "
                "TPU device (single chip, K logical shards).  "
                "`wallclock_s` is the SLOPE-MEASURED steady-state time "
                "for the row's rounds (fixed dispatch/fetch costs cancel "
                "between an R-round and an mR-round run); `fixed_s` is "
                "the cancelled per-run overhead — a raw stopwatch on one "
                "run reads ≈ wallclock_s + fixed_s ± its "
                "run-to-run jitter.  `vs_oracle` compares equal rounds "
                "against the single-thread NumPy oracle of the reference "
                "math; permuted-sampling rows instead report "
                "`vs_oracle_same_gap` (oracle at reference-mode rounds vs "
                "this row's wall-clock — a cross-mode comparison).  "
                "`vs_oracle_parallel` (and its same-gap twin) divides by "
                "the row's K: the speedup against an IDEAL K-way-parallel "
                "CPU run of the reference math — the honest denominator "
                "for the ≥10x north star (real Spark adds scheduling "
                "overhead on top, so the truth sits between the two "
                "columns).  Where that column reads < 1 the row is "
                "SUB-PARITY against an ideal parallel CPU baseline — "
                "true today of the reference-faithful rcv1 rows (~0.7x: "
                "single-thread CPUs are genuinely good at ~75-nnz "
                "sequential CSR steps); the headlined rcv1 config is the "
                "production row (permuted + σ′=auto + evalDense), which "
                "clears the bar on the comm-round levers.  See "
                "the module docstring for config definitions.\n\n"
                "Rows whose config lacks a `(real)` tag use the "
                "distribution-faithful **synthetic stand-in** from "
                "`data/synth.py` (matched n, d, nnz/row, row norms): "
                "`benchmarks/fetch_data.sh` is re-attempted every round "
                "and the build machine has no network route to the LIBSVM "
                "mirror, so the real rcv1/epsilon files cannot be "
                "fetched.  Real files dropped into benchmarks/data/ are "
                "picked up automatically and validated against the "
                "published (n, d, nnz/row) pins.  The fp "
                "(feature-parallel) capacity axis has no row here — it "
                "needs a multi-device mesh, and the attached TPU is one "
                "chip; its measured CPU-mesh per-round overhead ratio "
                "(one collective per coordinate step vs the dp path's "
                "one per round) is recorded in benchmarks/SWEEPS.md "
                "(benchmarks/fp_bench.py regenerates it).\n\n")
        f.write("| " + " | ".join(cols) + " |\n")
        f.write("|" + "---|" * len(cols) + "\n")
        for r in results:
            f.write("| " + " | ".join(
                "" if r.get(c) is None            # absent OR present-as-None
                else f"{r[c]:.4g}" if isinstance(r[c], float)
                else str(r[c]) for c in cols
            ) + " |\n")
        if perf_rows:
            f.write(
                "\n## Perf accounting (VERDICT r1 item 1)\n\n"
                "FLOP/byte models in `benchmarks/perf.py`; the accounting "
                "contract is the reference hot loop CoCoA.scala:148-188 "
                "(4·nnz useful FLOPs per coordinate step) plus the margins "
                "and eval passes of the measured path.  `useful` counts the "
                "reference's math; `physical` adds the FLOPs the TPU "
                "formulation spends to buy parallelism (block Gram work, "
                "lane padding).  MFU is against the chip's public dense "
                "bf16 peak — a conservative lower bound for f32 work.  "
                "Times include the per-`debugIter` eval amortized in; "
                "ms_per_round derives from the slope-measured steady "
                "state, so the per-run dispatch+fetch overhead is "
                "already cancelled (it is reported separately as the "
                "result table's fixed_s).\n\n"
            )
            pcols = ["config", "device", "ms_per_round", "us_per_step",
                     "useful_gflops", "physical_gflops", "mfu_pct",
                     "physical_mfu_pct", "hbm_floor_ms", "hbm_bound_pct",
                     "bound"]
            f.write("| " + " | ".join(pcols) + " |\n")
            f.write("|" + "---|" * len(pcols) + "\n")
            for r in perf_rows:
                f.write("| " + " | ".join(str(r.get(c, "")) for c in pcols)
                        + " |\n")
            bounds = [r.get("bound", "?") for r in perf_rows]
            n_lat = sum(1 for b in bounds if b == "latency")
            n_hbm = sum(1 for b in bounds if b == "HBM")
            n_mxu = sum(1 for b in bounds if b == "MXU")
            if n_lat == len(bounds):
                verdict = ("Every config is latency-bound: the measured "
                           "round time sits far above both the HBM-traffic "
                           "floor and the FLOP floor")
            else:
                # enumerate the actual mix — a fixed two-way phrasing
                # mislabeled MXU-bound rows as latency-bound (round-5
                # review finding)
                parts = []
                if n_hbm:
                    parts.append(f"{n_hbm} at the HBM-traffic floor")
                if n_mxu:
                    parts.append(f"{n_mxu} MXU-bound")
                if n_lat:
                    parts.append(f"{n_lat} latency-bound")
                other = len(bounds) - n_hbm - n_mxu - n_lat
                if other:
                    parts.append(f"{other} unclassified")
                verdict = (f"Of {len(bounds)} configs: "
                           + ", ".join(parts))
            f.write(
                f"\n{verdict}.  Where latency binds, the cause is the "
                "algorithm's hot loop — a sequential chain of O(nnz) "
                "coordinate steps (CoCoA.scala:148-188): per-step chain "
                "latency (see the us_per_step column and "
                "benchmarks/KERNELS.md), not bandwidth or MXU throughput, "
                "sets the ceiling.  Corollary: rcv1's round count to the "
                "1e-4 gap is λ=1e-4 *conditioning*, not sparse-kernel "
                "inefficiency — the same kernel reaches the 1e-3 gap in "
                "a fraction of the rounds.  Honest footnote on the rcv1 "
                "vs_oracle column: single-thread CPUs are genuinely good "
                "at ~75-nnz sequential CSR steps (sub-µs per step, all "
                "cache-resident), so the TPU's margin there is modest — "
                "the TPU case for sparse problems rests on the "
                "comm-round levers (σ′, reshuffling) and on scaling, "
                "not on beating a CPU at tiny sequential gathers; the "
                "dense configs are where the hardware's 100-1000× shows.\n"
                "\nRoofline reading, per config:\n\n"
            )
            for r in perf_rows:
                hbm = r.get("hbm_bound_pct")
                f.write(
                    f"- **{r['config']}** — {r['ms_per_round']} ms/round, "
                    f"{r['us_per_step']} µs per coordinate step "
                    f"(amortized over the K parallel shards); useful "
                    f"{r['useful_gflops']} GFLOP/s ≈ "
                    f"{r.get('mfu_pct', '?')}% MFU "
                    f"(physical {r.get('physical_mfu_pct', '?')}%).  The "
                    f"HBM-traffic model floor is {r.get('hbm_floor_ms', '?')} "
                    f"ms ({hbm}% of measured) → **{r.get('bound', '?')}-"
                    f"bound**.\n"
                )
    print(f"wrote {jl} and {md}")
    if not partial and final:
        for stale in ("results.inprogress.jsonl", "RESULTS.inprogress.md"):
            p = os.path.join(out_dir, stale)
            if os.path.exists(p):
                os.remove(p)
        _sync_docs(results)


def _sync_doc_block(path, text):
    """Replace the GENERATED:bench block in ``path`` (between the marker
    comments) with ``text``; no-op with a warning if markers are absent."""
    start = "<!-- GENERATED:bench -->"
    end = "<!-- /GENERATED:bench -->"
    with open(path) as f:
        s = f.read()
    if start not in s or end not in s:
        print(f"warning: {path} has no GENERATED:bench markers; skipped")
        return
    head, rest = s.split(start, 1)
    _, tail = rest.split(end, 1)
    with open(path, "w") as f:
        f.write(head + start + "\n" + text + end + tail)
    print(f"synced {path}")


def _sync_docs(results):
    """Regenerate the perf claims BASELINE.md and PARITY.md carry from the
    measured rows — one source of truth (VERDICT r2 item 2: three documents
    had three generations of numbers)."""
    by = {r["config"]: r for r in results}

    def lookup(cfg):
        # real-dataset runs label their configs e.g. rcv1(real)-... — the
        # claims should follow whichever variant actually ran
        return by.get(cfg.replace("epsilon", "epsilon(real)")
                      .replace("rcv1", "rcv1(real)")) or by.get(cfg)

    def row(cfg, label, extra=""):
        r = lookup(cfg)
        if r is None:
            return ""
        vs = r.get("vs_oracle")
        vs_s = f"≈{vs}× single-thread oracle" if vs is not None else \
            f"≈{r.get('vs_oracle_same_gap')}× same-gap vs oracle"
        par = (r.get("vs_oracle_parallel")
               if r.get("vs_oracle_parallel") is not None
               else r.get("vs_oracle_parallel_same_gap"))
        if par is not None:
            vs_s += f", ≈{par}× ideal-{r['k']}-way-parallel"
        fixed = r.get("fixed_s")
        return (f"| TPU rebuild: {label} | **{r['wallclock_s']} s steady "
                f"(+{fixed} s dispatch), {r['rounds']} comm-rounds** "
                f"({vs_s}{extra}) | 1 TPU chip, K={r['k']} | "
                f"benchmarks/results.jsonl |\n")

    base_rows = [
        row("demo-cocoa+", "demo config to 1e-4 gap"),
        row("epsilon-cocoa+(block128)",
            "epsilon-like 400K×2000 to 1e-4 gap (block kernel)",
            extra="; λ=1e-3, H=0.1·n/K"),
        row("epsilon-cocoa+(permuted+block128)",
            "epsilon, reshuffled sampling + block kernel"),
        row("rcv1-cocoa+(0.001)", "rcv1-like 20242×47236 sparse to 1e-3 gap"),
        row("rcv1-cocoa+(0.0001)", "rcv1-like sparse to 1e-4 gap"),
        row("rcv1-cocoa+(production: permuted+sigma=auto+evalDense)",
            "rcv1 production config (permuted + σ′=auto + evalDense) "
            "to 1e-4 gap"),
        row("lasso-proxcocoa+",
            "lasso 8192×32768 (ProxCoCoA+, λ=0.3λmax) to 1e-3 rel. gap"),
        row("elastic-proxcocoa+", "elastic net (l2=0.1), same design"),
    ]
    if all(base_rows):
        _sync_doc_block(os.path.join(ROOT, "BASELINE.md"),
                        "".join(base_rows))
    else:
        # a subset regen must never erase recorded rows (the other doc
        # blocks already guard this way)
        print("warning: BASELINE.md sync skipped — result set is missing "
              f"{sum(1 for r in base_rows if not r)} of the recorded "
              "configs")

    d = lookup("demo-cocoa+")
    e = lookup("epsilon-cocoa+(block128)")
    rc = lookup("rcv1-cocoa+(0.001)")
    if d and e and rc:
        par = (
            f"See BASELINE.md / benchmarks/results.jsonl (all numbers are "
            f"the slope-measured steady state; the per-run dispatch+fetch "
            f"overhead is reported separately as fixed_s):\n"
            f"demo config to the 1e-4 duality gap in {d['wallclock_s']} s "
            f"({d['rounds']} comm-rounds) on one TPU chip — "
            f"≈{d['vs_oracle']}× the single-threaded NumPy oracle of the "
            f"reference math (the Spark stack itself cannot run here; the "
            f"oracle has zero scheduler overhead, so the true Spark-vs-TPU "
            f"gap is larger); epsilon-scale (400K×2000) in "
            f"{e['wallclock_s']} s; rcv1-scale sparse (20242×47236) to "
            f"1e-3 in {rc['wallclock_s']} s.\n"
        )
        _sync_doc_block(os.path.join(ROOT, "PARITY.md"), par)

    eb = lookup("epsilon-cocoa+(block128)")
    ep = lookup("epsilon-cocoa+(permuted+block128)")
    r3 = lookup("rcv1-cocoa+(0.001)")
    r4 = lookup("rcv1-cocoa+(0.0001)")
    la = lookup("lasso-proxcocoa+")
    el = lookup("elastic-proxcocoa+")
    d0 = lookup("demo-cocoa+")
    dp = lookup("demo-cocoa+(permuted)")
    if all(x for x in (eb, ep, r3, r4, la, el, d0, dp)):
        readme = (
            f"Recorded single-chip results (benchmarks/results.jsonl, "
            f"round 5; wall-clocks are the slope-measured steady state — "
            f"the per-run dispatch overhead, reported separately as "
            f"fixed_s, would otherwise swamp the small configs): the "
            f"reference demo config in "
            f"**{d0['wallclock_s']} s** ({d0['rounds']} comm-rounds "
            f"reference-faithful, {dp['rounds']} with `--rng=permuted`); "
            f"epsilon-like dense 400K×2000 in **{eb['wallclock_s']} s** "
            f"({eb['rounds']} rounds with the fused block kernel; "
            f"**{ep['rounds']} rounds** with `--rng=permuted`, same "
            f"certified 1e-4 gap — comm-rounds are the baseline metric); "
            f"rcv1-like sparse 20242×47236 in **{r3['wallclock_s']} s** "
            f"to 1e-3 / **{r4['wallclock_s']} s** to 1e-4 "
            f"({r3['rounds']} / {r4['rounds']} rounds — the 1e-4 count "
            f"is λ=1e-4 conditioning, not kernel speed); lasso "
            f"8192×32768 via ProxCoCoA+ in **{la['wallclock_s']} s** to "
            f"a 1e-3 relative gap ({la['rounds']} rounds), elastic net "
            f"(l2={el.get('l2')}) in **{el['wallclock_s']} s** "
            f"({el['rounds']} rounds) with its smoothed-conjugate gap "
            f"certificate.  The `type: perf` rows of results.jsonl carry "
            f"the perf accounting (FLOPs, MFU, µs/coordinate-step, HBM "
            f"floor, roofline bound per config — the sequential coordinate "
            f"chain is the latency ceiling the `--blockSize` kernel "
            f"attacks).\n"
        )
        _sync_doc_block(os.path.join(ROOT, "README.md"), readme)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="~10x smaller synthetic sizes (smoke test)")
    ap.add_argument("--only", default="",
                    help="comma-separated subset: "
                         "demo,epsilon,rcv1,losses,lasso,ingest")
    ap.add_argument("--data-dir",
                    default=os.path.join(os.path.dirname(
                        os.path.abspath(__file__)), "data"),
                    help="directory holding real datasets (fetch_data.sh); "
                         "real files are preferred over synthetic stand-ins "
                         "and rows are labeled e.g. rcv1(real)")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    results = []
    perf_rows = []
    out_dir = os.path.dirname(os.path.abspath(__file__))
    partial = args.quick or only is not None

    printed = [0]

    def flush():
        # write after EVERY section: a device lost mid-suite must not lose
        # the sections already measured.  Print every not-yet-printed row (sections
        # append variable row counts; a fixed tail length dropped rows —
        # ADVICE r4).
        for r in results[printed[0]:]:
            print(json.dumps(r))
        printed[0] = len(results)
        write_results(results, perf_rows, out_dir, partial=partial)

    if only is None or "demo" in only:
        bench_demo(results, perf_rows)
        flush()
    if only is None or "epsilon" in only:
        bench_epsilon(results, perf_rows, args.quick, args.data_dir)
        flush()
    if only is None or "rcv1" in only:
        bench_rcv1(results, perf_rows, args.quick, args.data_dir)
        flush()
    if only is None or "losses" in only:
        bench_losses(results, perf_rows, args.quick)
        flush()
    if only is None or "lasso" in only:
        bench_lasso(results, perf_rows, args.quick)
        flush()
    if only is None or "ingest" in only:
        bench_ingest(results, args.quick)
        flush()
    write_results(results, perf_rows, out_dir, partial=partial, final=True)
    for r in perf_rows:
        print(json.dumps({"type": "perf", **r}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
