"""The serving benchmark: queries/s at a pinned p99 latency bound, plus
model freshness (gap age), on CPU.

The headline claim of the ``--serve`` path (docs/DESIGN.md §17): batched
margin queries ride a compiled scoring path with statically-shaped
buckets — one XLA compile per bucket, ever — behind an adaptive
micro-batcher, while the model hot-swaps under traffic without dropping
a request.  The bench trains a small model to a certified gap, serves
it from real checkpoint generations (one mid-run hot-swap, so the swap
machinery is in the measured path), hammers the batcher from G client
threads for the duration, and reports

- ``qps``       — answered requests / wall-clock of the traffic window
- ``p50/p99_ms``— per-request latency percentiles (submit → answer),
  measured exactly (every request's own enqueue timestamp)
- ``sla_ms``    — the pinned bound: the run FAILS (exit 1) if p99
  exceeds it — the row is "queries/s AT p99 ≤ SLA", not queries/s alone
- ``gap_age_s`` — the serving model's certificate age at measurement
  end (freshness, the cocoa_model_gap_age_seconds gauge's value)
- ``compiles``  — measured XLA compiles of the scoring executable
  (must equal the bucket count: the one-compile-per-bucket pin)

    python benchmarks/serve_bench.py                 # print the row
    python benchmarks/serve_bench.py --row=out.jsonl # write it

Latency/qps are CPU-measured host wall-clock: the server child is pinned
to the CPU backend, so no number here is a device metric (ROADMAP R3
measures serving on the chip).

``--serveDtype=bf16|int8`` switches to the low-precision A/B mode
(docs/DESIGN.md §20): compiled-path margin throughput of the packed
quantized model vs the SAME-harness f32 control, at a serving-scale
geometry chosen so the mechanism under test is the real one — the f32
model (2.5 MB) spills L2 while the packed bf16 form (1.25 MB) fits, so
halving the gather stream is what the ratio measures.  XLA's CPU
backend EMULATES narrow arithmetic (a plain bf16 model is SLOWER than
f32), which is why the small-model serving row above would show ~1.0x:
the win appears exactly when the model stops fitting in cache, and on
TPU the same packed layout halves the HBM stream instead.  The A/B row
(``serve-cpu-synth-bf16``) carries the same-harness control
(``f32_qps``), the measured ``qps_ratio``, the per-swap certificate
(``margin_err_bound`` over the calibration batch) and a sign-flip
audit over a disjoint validation set (``flips`` beyond 2x the bound
must be 0); the mid-bench hot-swap quantizes IN the measured path and
the compile count pins one executable per (bucket, dtype) per scorer.
"""

from __future__ import annotations

import argparse
import json
import os
import queue
import signal
import socket
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CONFIG = "serve-cpu-synth"
# the canonical serving workload: a small certified model, sparse
# queries (nnz ~ 12 of d=256), two buckets, a 50 ms p99 SLA
N, D, K = 2048, 256, 2
LAM, GAP_TARGET = 1e-3, 1e-2
BUCKETS = (64, 256)
MAX_NNZ = 32
SLA_MS = 50.0
QUERY_NNZ = 12

# the --serveDtype A/B geometry: one saturated bucket of nnz-512
# queries against a model sized at the L2 knife edge of this class of
# serving CPU — f32 w = 2.5 MB spills a ~2 MB L2, packed bf16 = 1.25 MB
# fits — so the measured ratio is the gather-stream halving, the same
# mechanism that halves the HBM stream at TPU scale
D_Q = 640 * 1024
BUCKET_Q = 1024
NNZ_Q = 512
N_BATCHES_Q = 8     # distinct preassembled query batches cycled through
CALIB_N = 64        # calibration queries the certificate is bound over
# one executable per (bucket, dtype) per scorer instance: the f32
# control scorer compiles its one form; the quantized scorer compiles
# its packed form plus the f32 certificate-fallback form
EXPECTED_COMPILES_Q = 3

# the --serveReplicas fleet mode (docs/DESIGN.md §21): R real CLI
# replica processes serving a (T, d) tenant catalogue behind the
# in-bench router, hammered over real sockets with tenant-tagged
# multi-query lines; the headline is aggregate answered queries/s vs
# the SAME-harness 1-replica control (scaling_eff), plus the open-loop
# overload window's shed accounting and the SIGKILL recovery drill
T_FLEET = 4
Q_PER_LINE = 16     # ';'-separated queries per protocol line
FLEET_LINES = 64    # distinct preassembled lines cycled per client
# the tracing A/B (docs/DESIGN.md §22): the fleet row carries
# a tracing-on closed-loop window (every line trace=-prefixed, the
# router samples 1 in TRACE_SAMPLE into query_trace events) against a
# back-to-back untraced window of the same shape; the overhead of the
# always-paid prefix peel + the sampled stamp/emit path must stay
# under the --trace-bar (default 5%)
TRACE_SAMPLE = 64
TRACE_BAR_PCT = 5.0


def train_checkpoints(ck: str):
    """Train the model to its certified gap and leave TWO checkpoint
    generations (the second is the mid-bench hot-swap target)."""
    import numpy as np

    from cocoa_tpu import checkpoint as ckpt_lib
    from cocoa_tpu.config import DebugParams, Params
    from cocoa_tpu.data.sharding import shard_dataset
    from cocoa_tpu.data.synth import synth_dense
    from cocoa_tpu.solvers import run_cocoa

    data = synth_dense(N, D, seed=7)
    ds = shard_dataset(data, k=K, layout="dense")
    params = Params(n=N, num_rounds=300, local_iters=max(1, N // K // 10),
                    lam=LAM, gamma=1.0, loss="hinge")
    debug = DebugParams(debug_iter=10, seed=0, chkpt_iter=301,
                        chkpt_dir="")
    w, alpha, traj = run_cocoa(ds, params, debug, plus=True, quiet=True,
                               gap_target=GAP_TARGET)
    gap = traj.records[-1].gap if traj.records else None
    rounds = traj.records[-1].round if traj.records else 0
    w = np.asarray(w)
    # generation 1: the model the server starts on; generation 2: the
    # fresher state the watcher hot-swaps in mid-bench (a genuinely
    # different iterate — here the final w vs a perturbed older one)
    ckpt_lib.save(ck, "CoCoA+", max(1, rounds - 10),
                  (w * 0.95).astype(np.float32), None, gap=gap)
    return w.astype(np.float32), rounds, gap


def measure(ck, w_final, rounds, gap, duration_s: float, threads: int,
            sla_ms: float):
    import numpy as np

    from cocoa_tpu import checkpoint as ckpt_lib
    from cocoa_tpu import serving
    from cocoa_tpu.analysis import sanitize

    with sanitize.watch_compiles() as compiles:
        w0, info = serving.load_model(ckpt_lib.latest(ck, "CoCoA+"))
        slots = serving.ModelSlots(w0, info, dtype=np.float32)
        scorer = serving.BatchScorer(D, dtype=np.float32,
                                     buckets=BUCKETS, max_nnz=MAX_NNZ)
        scorer.warmup(slots.current()[0])
        batcher = serving.MicroBatcher(scorer, slots,
                                       sla_s=sla_ms / 1000.0)
        watcher = serving.SwapWatcher(slots, ck, "CoCoA+",
                                      poll_s=0.05).start()
        stop = threading.Event()
        lock = threading.Lock()
        lats = []
        failed = [0]

        def client(seed):
            rng = np.random.default_rng(seed)
            while not stop.is_set():
                idx = np.sort(rng.choice(D, size=QUERY_NNZ,
                                         replace=False)).astype(np.int32)
                val = rng.standard_normal(QUERY_NNZ)
                t0 = time.monotonic()
                try:
                    batcher.score_sync(idx, val, timeout=10.0)
                except Exception:
                    with lock:
                        failed[0] += 1
                    continue
                with lock:
                    lats.append(time.monotonic() - t0)

        workers = [threading.Thread(target=client, args=(s,),
                                    daemon=True)
                   for s in range(threads)]
        t_start = time.monotonic()
        for t in workers:
            t.start()
        # the mid-bench hot-swap: the trainer "catches up" halfway in
        time.sleep(duration_s / 2)
        ckpt_lib.save(ck, "CoCoA+", rounds, w_final, None, gap=gap)
        time.sleep(duration_s / 2)
        stop.set()
        for t in workers:
            t.join(10)
        wall = time.monotonic() - t_start
        watcher.stop()
        gap_age = slots.gap_age_s()
        swaps = watcher.swaps_total
        batcher.stop()
    serve_compiles = sum(1 for c in compiles
                         if "serve_margins" in c.name)
    lats.sort()

    def pct(p):
        return lats[min(len(lats) - 1, int(p * len(lats)))] * 1000.0

    return {
        "config": CONFIG, "type": "serve", "device": "cpu",
        "n": N, "d": D, "k": K, "lam": LAM,
        "gap": gap, "gap_target": GAP_TARGET, "rounds": int(rounds),
        "queries": len(lats), "threads": threads,
        "qps": round(len(lats) / wall, 1),
        "p50_ms": round(pct(0.50), 3), "p99_ms": round(pct(0.99), 3),
        "sla_ms": sla_ms,
        "fill": round(batcher.requests_total
                      / max(1, batcher.slots_total), 3),
        "buckets": "/".join(str(b) for b in BUCKETS),
        "compiles": serve_compiles, "swaps": swaps,
        "gap_age_s": round(gap_age, 3),
        "wallclock_s": round(wall, 3),
        "stopped": ("target" if failed[0] == 0 and swaps >= 1
                    else None),
    }


def _quant_batches(rng, n_batches):
    """Preassembled nnz-512 query batches (host f32/int32 pairs)."""
    import numpy as np

    batches = []
    for _ in range(n_batches):
        idx = rng.integers(0, D_Q, size=(BUCKET_Q, NNZ_Q),
                           dtype=np.int64).astype(np.int32)
        val = rng.standard_normal((BUCKET_Q, NNZ_Q)).astype(np.float32)
        batches.append((idx, val))
    return batches


def _pass_qps(scorer, slots, batches, pass_s, lats=None):
    """One timed pass: sustained rows/s of the compiled path, cycling
    the preassembled batches; each dispatch blocks on the fetched
    margins so the number is end-to-end dispatch+compute+fetch."""
    import numpy as np

    w_dev, scale, _ = slots.current()
    n = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < pass_s:
        idx, val = batches[n % len(batches)]
        t1 = time.perf_counter()
        np.asarray(scorer.score(w_dev, idx, val, scale=scale))
        if lats is not None:
            lats.append(time.perf_counter() - t1)
        n += 1
    return n * BUCKET_Q / (time.perf_counter() - t0)


def measure_quant(serve_dtype: str, duration_s: float, sla_ms: float):
    """The --serveDtype A/B row: packed-``serve_dtype`` compiled-path
    throughput vs the same-harness f32 control, with the mid-measure
    hot-swap (quantize-at-swap in the measured path), the calibration
    certificate, and the disjoint sign-flip audit."""
    import jax
    import numpy as np

    from cocoa_tpu import checkpoint as ckpt_lib
    from cocoa_tpu import serving
    from cocoa_tpu.analysis import sanitize
    from cocoa_tpu.serving import quantize as quant_lib

    rng = np.random.default_rng(11)
    # a synthetic serving-scale model (training to certification at
    # d=640K is a training bench, not a serving one) shipped through
    # real checkpoint generations so load/swap stay the product path
    w_final = (rng.standard_normal(D_Q) * 0.05).astype(np.float32)
    ck = tempfile.mkdtemp(prefix="serve-bench-quant-")
    ckpt_lib.save(ck, "CoCoA+", 1, (w_final * 0.97).astype(np.float32),
                  None, gap=GAP_TARGET)
    batches = _quant_batches(rng, N_BATCHES_Q)
    pass_s = max(0.2, duration_s / 10.0)

    with sanitize.watch_compiles() as compiles:
        w0, info = serving.load_model(ckpt_lib.latest(ck, "CoCoA+"))
        # calibration from the bench's own query stream: the first
        # CALIB_N rows of batch 0 (the flip audit below uses the OTHER
        # batches — bound and audit are disjoint)
        calib = serving.CalibrationBuffer(D_Q, max_nnz=NNZ_Q,
                                          capacity=CALIB_N, seed=11)
        for r in range(CALIB_N):
            calib.record(batches[0][0][r], batches[0][1][r])
        slots_f32 = serving.ModelSlots(w0, info, dtype="f32")
        scorer_f32 = serving.BatchScorer(D_Q, dtype="f32",
                                         buckets=(BUCKET_Q,),
                                         max_nnz=NNZ_Q)
        scorer_f32.warmup(slots_f32.current()[0])
        slots_q = serving.ModelSlots(w0, info, dtype=serve_dtype,
                                     calibration=calib)
        scorer_q = serving.BatchScorer(D_Q, dtype=serve_dtype,
                                       buckets=(BUCKET_Q,),
                                       max_nnz=NNZ_Q)
        wq_dev, q_scale, _ = slots_q.current()
        scorer_q.warmup(wq_dev, q_scale)
        watcher = serving.SwapWatcher(slots_q, ck, "CoCoA+",
                                      poll_s=0.05)

        t_start = time.monotonic()
        dev_batches = [(jax.device_put(i), jax.device_put(v))
                       for i, v in batches]
        # one steady-state dispatch per arm before timing
        np.asarray(scorer_f32.score(slots_f32.current()[0],
                                    *dev_batches[0]))
        np.asarray(scorer_q.score(wq_dev, *dev_batches[0],
                                  scale=q_scale))
        # the arms INTERLEAVE pass-by-pass and the gate is the median
        # of the pairwise ratios: the f32 control straddles L2 by
        # design, so its absolute rate is bimodal with machine state —
        # pairing each quantized pass with an adjacent control pass
        # cancels the slow drift a best-of-separated-arms design
        # mistakes for a precision effect
        pairs = 6
        lats = []
        f32_rates, q_rates = [], []
        for p in range(pairs):
            f32_rates.append(_pass_qps(scorer_f32, slots_f32,
                                       dev_batches, pass_s))
            q_rates.append(_pass_qps(scorer_q, slots_q, dev_batches,
                                     pass_s, lats=lats))
            if p == pairs // 2 - 1:
                # the mid-measure hot-swap: gen-2 lands, slots_q
                # quantizes and re-certifies it, and the remaining
                # passes serve the new bytes
                ckpt_lib.save(ck, "CoCoA+", 2, w_final, None,
                              gap=GAP_TARGET)
                watcher.poll_once()
        ratios = sorted(q / f for q, f in zip(q_rates, f32_rates))
        qps_ratio = ratios[len(ratios) // 2]
        qps = sorted(q_rates)[len(q_rates) // 2]
        f32_qps = sorted(f32_rates)[len(f32_rates) // 2]
        wall = time.monotonic() - t_start
        swaps = watcher.swaps_total
        served = slots_q.served_dtype
        bound = slots_q.last_bound
    serve_compiles = sum(1 for c in compiles
                         if "serve_margins" in c.name)

    # the sign-flip audit, host f64, on batches DISJOINT from the
    # calibration the bound came from: a flip at |m32| > 2x bound means
    # the certificate understated the error — the gate is 0
    qm = quant_lib.quantize(w_final, serve_dtype)
    # jaxlint: allow=f64 -- host-side certificate audit arithmetic
    w_served = quant_lib.dequantize(qm, D_Q).astype(np.float64)
    w64 = w_final.astype(np.float64)  # jaxlint: allow=f64 -- audit
    flips = 0
    flip_checked = 0
    for idx, val in batches[1:]:
        m32 = (w64[idx] * val).sum(axis=1)
        mq = (w_served[idx] * val).sum(axis=1)
        flip_checked += len(m32)
        guarded = np.abs(m32) > 2.0 * float(bound)
        flips += int(np.sum(guarded & (np.sign(m32) != np.sign(mq))))

    lats.sort()

    def pct(p):
        return lats[min(len(lats) - 1, int(p * len(lats)))] * 1000.0

    return {
        "config": f"{CONFIG}-{serve_dtype}", "type": "serve",
        "device": "cpu", "d": D_Q, "serve_dtype": serve_dtype,
        "queries": flip_checked + len(batches[0][0]),
        "qps": round(qps, 1), "f32_qps": round(f32_qps, 1),
        "qps_ratio": round(qps_ratio, 3),
        "p50_ms": round(pct(0.50), 3), "p99_ms": round(pct(0.99), 3),
        "sla_ms": sla_ms,
        "buckets": str(BUCKET_Q),
        "compiles": serve_compiles, "swaps": swaps,
        "margin_err_bound": float(bound),
        "flips": flips, "flip_checked": flip_checked,
        "calib_n": CALIB_N,
        "wallclock_s": round(wall, 3),
        "stopped": ("target" if swaps >= 1 and flips == 0
                    and served == serve_dtype else None),
    }


def _fleet_lines(rng, n_lines):
    """Preassembled tenant-tagged protocol lines: each carries
    ``Q_PER_LINE`` nnz-12 queries for one tenant, tenants round-robin
    across lines so every window is cross-tenant traffic."""
    import numpy as np

    lines = []
    for j in range(n_lines):
        qs = []
        for _ in range(Q_PER_LINE):
            idx = np.sort(rng.choice(D, size=QUERY_NNZ, replace=False))
            val = rng.standard_normal(QUERY_NNZ)
            qs.append(" ".join(f"{int(i)}:{float(v):.5f}"
                               for i, v in zip(idx, val)))
        lines.append((f"tenant={j % T_FLEET};" + ";".join(qs)
                      + "\n").encode())
    return lines


def _traced_lines(lines):
    """The tracing-on A/B variant: the SAME preassembled lines with a
    client-chosen ``trace=<hex>;`` id prefixed (docs/DESIGN.md §22) —
    the router peels every prefix and samples 1 in ``TRACE_SAMPLE``
    into ``query_trace`` events."""
    return [b"trace=%08x;" % j + ln for j, ln in enumerate(lines)]


class _ClientStats:
    def __init__(self):
        self.lock = threading.Lock()
        self.answered = 0    # queries (lines x Q_PER_LINE)
        self.shed = 0        # lines refused at admission
        self.failed = 0      # lines that got an error / dead socket
        self.lats = []       # per-line seconds, answered lines only

    def record(self, resp, dt):
        with self.lock:
            if isinstance(resp, list):
                self.answered += len(resp)
                self.lats.append(dt)
            elif isinstance(resp, dict) and resp.get("shed"):
                self.shed += 1
            else:
                self.failed += 1


def _ask_lines(addr, lines, stats, stop_ev, stride, offset):
    """One closed-loop client connection: send, read, classify, repeat
    until stopped."""
    try:
        s = socket.create_connection(addr, timeout=30)
        s.settimeout(60)
    except OSError:
        with stats.lock:
            stats.failed += 1
        return
    f = s.makefile("rwb")
    n = offset
    while not stop_ev.is_set():
        line = lines[n % len(lines)]
        n += stride
        t0 = time.monotonic()
        try:
            f.write(line)
            f.flush()
            resp = json.loads(f.readline())
        except (OSError, ValueError):
            with stats.lock:
                stats.failed += 1
            break
        stats.record(resp, time.monotonic() - t0)
    try:
        s.close()
    except OSError:
        pass


def _closed_window(addr, lines, n_conn, duration_s, midpoint=None):
    """Closed-loop capacity window: ``n_conn`` connections back to
    back; ``midpoint`` (if given) runs at the half mark — the mid-bench
    catalogue hot-swap rides it."""
    stats = _ClientStats()
    stop_ev = threading.Event()
    workers = [threading.Thread(target=_ask_lines,
                                args=(addr, lines, stats, stop_ev,
                                      n_conn, c), daemon=True)
               for c in range(n_conn)]
    t0 = time.monotonic()
    for t in workers:
        t.start()
    time.sleep(duration_s / 2)
    if midpoint is not None:
        midpoint()
    time.sleep(duration_s / 2)
    stop_ev.set()
    for t in workers:
        t.join(30)
    return stats, time.monotonic() - t0


def _open_window(addr, lines, n_senders, duration_s, rate_qps):
    """Open-loop overload window: a pacer enqueues line tickets at the
    offered rate regardless of completions (no coordinated omission);
    senders drain against the router, whose admission control sheds
    rather than queueing into an SLA violation."""
    stats = _ClientStats()
    stop_ev = threading.Event()
    tickets: "queue.Queue" = queue.Queue()
    offered = [0]

    def pacer():
        period = Q_PER_LINE / rate_qps
        nxt = time.monotonic()
        end = nxt + duration_s
        while time.monotonic() < end:
            tickets.put(offered[0])
            offered[0] += 1
            nxt += period
            pause = nxt - time.monotonic()
            if pause > 0:
                time.sleep(pause)
        stop_ev.set()

    def sender():
        try:
            s = socket.create_connection(addr, timeout=30)
            s.settimeout(60)
        except OSError:
            with stats.lock:
                stats.failed += 1
            return
        f = s.makefile("rwb")
        while True:
            try:
                i = tickets.get(timeout=0.2)
            except queue.Empty:
                if stop_ev.is_set():
                    break
                continue
            t0 = time.monotonic()
            try:
                f.write(lines[i % len(lines)])
                f.flush()
                resp = json.loads(f.readline())
            except (OSError, ValueError):
                with stats.lock:
                    stats.failed += 1
                break
            stats.record(resp, time.monotonic() - t0)
        try:
            s.close()
        except OSError:
            pass

    threads = [threading.Thread(target=pacer, daemon=True)]
    threads += [threading.Thread(target=sender, daemon=True)
                for _ in range(n_senders)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join(duration_s + 120)
    return stats, time.monotonic() - t0, offered[0] * Q_PER_LINE


def _paired_window(addr, lines_a, lines_b, n_conn, duration_s):
    """The A/B inside ONE window: every connection strictly alternates
    an A line and a B line, so both arms sample identical machine
    conditions — scheduler drift, background compiles, and neighbor
    load cancel exactly instead of landing on one arm (the interleaved
    back-to-back form showed ±10% between identical windows on a busy
    runner).  Per-arm closed-loop throughput is reconstructed from the
    per-arm service time (the sum of that arm's own latencies)."""
    stats = (_ClientStats(), _ClientStats())
    stop_ev = threading.Event()

    def worker(offset):
        try:
            s = socket.create_connection(addr, timeout=30)
            s.settimeout(60)
        except OSError:
            with stats[0].lock:
                stats[0].failed += 1
            return
        f = s.makefile("rwb")
        arms = (lines_a, lines_b)
        n, k = offset, 0
        while not stop_ev.is_set():
            arm = k % 2
            k += 1
            line = arms[arm][n % len(arms[arm])]
            if arm == 1:
                n += n_conn
            t0 = time.monotonic()
            try:
                f.write(line)
                f.flush()
                resp = json.loads(f.readline())
            except (OSError, ValueError):
                with stats[arm].lock:
                    stats[arm].failed += 1
                break
            stats[arm].record(resp, time.monotonic() - t0)
        try:
            s.close()
        except OSError:
            pass

    workers = [threading.Thread(target=worker, args=(c,), daemon=True)
               for c in range(n_conn)]
    for t in workers:
        t.start()
    time.sleep(duration_s)
    stop_ev.set()
    for t in workers:
        t.join(30)
    return stats


def _fleet_harness(ck, n_replicas, route, sla_ms, evdir, tag,
                   trace_sample=0):
    """Spawn ``n_replicas`` REAL CLI serve processes against the
    catalogue and put a router in front (the same classes the CLI
    fleet path composes)."""
    from cocoa_tpu.serving.fleet import ServeFleet
    from cocoa_tpu.serving.router import Router

    fleet = ServeFleet(
        [f"--chkptDir={ck}", f"--numFeatures={D}",
         "--serveBatch=" + ",".join(str(b) for b in BUCKETS),
         f"--serveSlaMs={sla_ms:g}", f"--serveMaxNnz={MAX_NNZ}",
         "--quiet"],
        n_replicas,
        extra_argv_fn=lambda i: [f"--events={evdir}/{tag}{i}.jsonl"],
        # the persistent XLA cache would hide warmup compiles from the
        # one-compile-per-bucket accounting — count real compiles
        env={"JAX_PLATFORMS": "cpu", "COCOA_NO_COMPILE_CACHE": "1"})
    router = Router(fleet.start(), sla_s=sla_ms / 1000.0, route=route,
                    trace_sample=trace_sample)
    fleet.attach(router)
    threading.Thread(target=router.serve_forever, daemon=True).start()
    return fleet, router


def _replica_stream_counts(path):
    """(serve_margins compiles, injected-swap events) in one replica's
    event stream."""
    compiles = swaps = 0
    if os.path.exists(path):
        for ln in open(path):
            r = json.loads(ln)
            if (r.get("event") == "compile"
                    and "serve_margins" in r.get("name", "")):
                compiles += 1
            elif (r.get("event") == "model_swap"
                  and r.get("round") == 2):
                swaps += 1
    return compiles, swaps


def measure_fleet(n_replicas, route, duration_s, threads, sla_ms,
                  rate_qps):
    """The ``--serveReplicas`` row: aggregate socket-path qps of R
    replicas vs the same-harness 1-replica control, the open-loop
    overload window's shed accounting, and the SIGKILL recovery drill
    (requeue, respawn, zero failed queries)."""
    import numpy as np

    from cocoa_tpu import checkpoint as ckpt_lib

    from cocoa_tpu.telemetry import events as tele_events

    rng = np.random.default_rng(23)
    w_cat = (rng.standard_normal((T_FLEET, D)) * 0.05).astype(
        np.float32)
    ck = tempfile.mkdtemp(prefix="serve-bench-fleet-")
    # per-tenant certification metadata rides the catalogue checkpoint
    # (docs/DESIGN.md §22): the replicas' tenant-labelled gap-age
    # gauges are fed from it, so the bench writes what a fleet trainer
    # would
    ckpt_lib.save(ck, "CoCoA+", 1, (w_cat * 0.95).astype(np.float32),
                  None, gap=GAP_TARGET,
                  tenant_gaps=[GAP_TARGET] * T_FLEET,
                  tenant_cert_ts=[time.time()] * T_FLEET)
    evdir = tempfile.mkdtemp(prefix="serve-bench-fleet-ev-")
    # the in-bench router emits the fleet-side query_trace events; give
    # its bus a stream so the traces are a real artifact
    router_ev = f"{evdir}/router.jsonl"
    tele_events.get_bus().configure(jsonl_path=router_ev)
    lines = _fleet_lines(rng, FLEET_LINES)
    traced = _traced_lines(lines)
    n_conn = max(4, threads)
    t_start = time.monotonic()

    print(f"serve_bench: spawning {n_replicas} fleet replicas "
          f"(catalogue {w_cat.shape}, route={route})", flush=True)
    fleet, router = _fleet_harness(ck, n_replicas, route, sla_ms,
                                   evdir, "rep",
                                   trace_sample=TRACE_SAMPLE)
    try:
        # --- capacity: closed loop, catalogue hot-swap at the half ---
        cap, cap_wall = _closed_window(
            router.address, lines, n_conn, duration_s,
            midpoint=lambda: ckpt_lib.save(
                ck, "CoCoA+", 2, w_cat, None, gap=GAP_TARGET,
                tenant_gaps=[GAP_TARGET] * T_FLEET,
                tenant_cert_ts=[time.time()] * T_FLEET))
        qps = cap.answered / cap_wall
        print(f"serve_bench: fleet capacity {qps:.0f} qps "
              f"({cap.answered} answered / {cap_wall:.2f}s)",
              flush=True)

        # --- tracing A/B: trace=-prefixed lines vs the same window ---
        # one paired window, lines alternating per connection; the
        # traced arm pays the per-line prefix peel on every line and
        # the stamp/emit path on the sampled 1-in-TRACE_SAMPLE.  The
        # overhead is the per-line mean-latency ratio of the two arms
        trc, ab = _paired_window(router.address, traced, lines,
                                 n_conn, duration_s)
        trc_failed, ab_failed = trc.failed, ab.failed
        t_mean = sum(trc.lats) / max(1, len(trc.lats))
        u_mean = sum(ab.lats) / max(1, len(ab.lats))
        traced_qps = trc.answered / max(1e-9, sum(trc.lats) / n_conn)
        trace_overhead_pct = round(
            max(0.0, 100.0 * (t_mean / max(1e-9, u_mean) - 1.0)), 2)
        print(f"serve_bench: tracing A/B {traced_qps:.0f} qps traced, "
              f"per-line {t_mean * 1e3:.3f}ms traced vs "
              f"{u_mean * 1e3:.3f}ms untraced "
              f"({trace_overhead_pct:g}% overhead)", flush=True)

        # --- overload: open loop past capacity — shed, don't queue ---
        if rate_qps <= 0:
            rate_qps = round(4 * qps)
        over, _, offered = _open_window(router.address, lines,
                                        2 * n_conn, duration_s / 2,
                                        rate_qps)
        print(f"serve_bench: overload window offered {offered} "
              f"queries at {rate_qps:g} qps — "
              f"{over.answered} answered, {over.shed} lines shed",
              flush=True)

        # both replicas must observe the injected generation before
        # the kill drill (the victim's swap event dies with it)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if all(_replica_stream_counts(
                    f"{evdir}/rep{i}.jsonl")[1] >= 1
                   for i in range(n_replicas)):
                break
            time.sleep(0.5)

        # --- the SIGKILL drill: requeue + respawn, zero failures -----
        # the connection is opened BEFORE the kill and the lines go out
        # sequentially right after it, so the first ones race the fleet
        # monitor to the dead replica — the requeue path, not just the
        # rerouted one, is in the drill
        drill = _ClientStats()
        victim = fleet.replicas[0]
        s = socket.create_connection(router.address, timeout=30)
        s.settimeout(60)
        sf = s.makefile("rwb")
        os.kill(victim.pid, signal.SIGKILL)
        print(f"serve_bench: SIGKILLed replica r0 (pid {victim.pid})",
              flush=True)
        for j in range(30):
            t0 = time.monotonic()
            sf.write(lines[j % len(lines)])
            sf.flush()
            drill.record(json.loads(sf.readline()),
                         time.monotonic() - t0)
        s.close()
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline and (
                victim.restarts < 1
                or router.replicas_live() < n_replicas):
            time.sleep(0.5)
        respawned = (victim.restarts >= 1
                     and router.replicas_live() == n_replicas)
        tail, _ = _closed_window(router.address, lines, 2, 1.0)
        print(f"serve_bench: kill window answered "
              f"{drill.answered + tail.answered} queries, "
              f"respawned={respawned}", flush=True)

        processes = [1 + r.restarts for r in fleet.replicas]
        shed_total = int(router.shed_total)
        requeued = int(router.requeue_total)
        failed = (int(router.failed_total) + cap.failed + over.failed
                  + trc_failed + ab_failed
                  + drill.failed + tail.failed)
    finally:
        router.stop()
        fleet.stop()
        router.close()

    # --- same-harness 1-replica control --------------------------------
    print("serve_bench: measuring the 1-replica control", flush=True)
    ctl_fleet, ctl_router = _fleet_harness(ck, 1, "rr", sla_ms, evdir,
                                           "ctl")
    try:
        ctl, ctl_wall = _closed_window(ctl_router.address, lines,
                                       n_conn, duration_s)
        failed += ctl.failed + int(ctl_router.failed_total)
    finally:
        ctl_router.stop()
        ctl_fleet.stop()
        ctl_router.close()
        tele_events.get_bus().reset()
    control_qps = ctl.answered / ctl_wall

    # --- the sampled-trace artifact: schema-valid, assemblable -------
    # the row commits the waterfall's verdict (the dominant hop), so a
    # regression that stops traces from assembling fails the gate, not
    # just the dashboard
    from cocoa_tpu.telemetry import schema as tele_schema
    from cocoa_tpu.telemetry import trace_report

    trace_errs = tele_schema.check_file(router_ev)
    if trace_errs:
        print(f"serve_bench: trace stream schema violations: "
              f"{trace_errs[:3]}", file=sys.stderr)
    qts = trace_report.load_query_traces([router_ev])
    wf = trace_report.query_waterfall(qts) if qts else None
    dominant = wf["dominant_hop"] if wf else None
    if wf:
        print(f"serve_bench: {len(qts)} sampled traces — dominant hop "
              f"{dominant} (p99 "
              f"{wf['hops'][dominant]['p99_s'] * 1000.0:.3f}ms)",
              flush=True)

    counts = [_replica_stream_counts(f"{evdir}/rep{i}.jsonl")
              for i in range(n_replicas)]
    # each replica PROCESS compiles one executable per bucket; the
    # respawned victim appends its own warmup to the same stream, so
    # divide by the process count before comparing across replicas
    per_proc = set()
    for (c, _), p in zip(counts, processes):
        per_proc.add(c // p if c % p == 0 else -1)
    compiles = per_proc.pop() if len(per_proc) == 1 else -1
    swaps = sum(1 for _, s in counts if s >= 1)

    lats = sorted(cap.lats)

    def pct(p):
        return lats[min(len(lats) - 1, int(p * len(lats)))] * 1000.0

    return {
        "config": "serve-cpu-fleet", "type": "serve", "device": "cpu",
        "d": D, "tenants": T_FLEET, "replicas": n_replicas,
        "route": route, "threads": n_conn,
        "queries": cap.answered,
        "qps": round(qps, 1),
        "control_qps": round(control_qps, 1),
        "scaling_eff": round(qps / (n_replicas * control_qps), 3),
        "rate_qps": float(rate_qps),
        "traced_qps": round(traced_qps, 1),
        "trace_overhead_pct": trace_overhead_pct,
        "trace_sampled": len(qts),
        "trace_schema_errors": len(trace_errs),
        "dominant_hop": dominant,
        "shed": shed_total, "requeued": requeued, "failed": failed,
        "p50_ms": round(pct(0.50), 3), "p99_ms": round(pct(0.99), 3),
        "sla_ms": sla_ms,
        "buckets": "/".join(str(b) for b in BUCKETS),
        "compiles": compiles, "swaps": swaps, "killed": 1,
        "wallclock_s": round(time.monotonic() - t_start, 3),
        "stopped": ("target" if failed == 0 and respawned
                    and swaps >= n_replicas
                    and compiles == len(BUCKETS) else None),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--row", default=None,
                    help="write the results row to this JSONL path")
    ap.add_argument("--duration", type=float, default=4.0,
                    help="traffic window seconds (default 4)")
    ap.add_argument("--threads", type=int, default=4)
    ap.add_argument("--sla-ms", type=float, default=SLA_MS)
    ap.add_argument("--serveDtype", default="f32",
                    choices=("f32", "bf16", "int8"),
                    help="f32 = the canonical serving row; bf16/int8 = "
                         "the low-precision A/B row vs an f32 control")
    ap.add_argument("--ratio-bar", type=float, default=1.7,
                    help="qps_ratio bar for the A/B self-gate (1.7 is "
                         "the acceptance bar)")
    ap.add_argument("--correctness-only", action="store_true",
                    help="skip the qps_ratio bar and gate only the "
                         "correctness axes (flips / compiles / swap): "
                         "the int8 A/B row commits under this — XLA's "
                         "CPU backend emulates int8 unpack, so its CPU "
                         "throughput is not the claim, the certificate "
                         "machinery is")
    ap.add_argument("--serveReplicas", type=int, default=0,
                    help="fleet mode: spawn this many REAL CLI scorer "
                         "replicas behind the router and measure "
                         "aggregate qps vs a 1-replica control "
                         "(the serve-cpu-fleet row)")
    ap.add_argument("--route", default="tenant",
                    choices=("rr", "tenant"),
                    help="fleet routing policy for the fleet row")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="open-loop offered rate (queries/s) for the "
                         "fleet overload window; 0 = 4x the measured "
                         "capacity")
    ap.add_argument("--trace-bar", type=float, default=TRACE_BAR_PCT,
                    help="max tracing-on qps overhead (%%) the fleet "
                         "A/B may show (default: the 5%% acceptance "
                         "bar)")
    args = ap.parse_args(argv)

    if args.serveReplicas >= 2:
        row = measure_fleet(args.serveReplicas, args.route,
                            args.duration, args.threads, args.sla_ms,
                            args.rate)
        print(json.dumps(row))
        if args.row:
            with open(args.row, "w") as f:
                f.write(json.dumps(row) + "\n")
        failures = []
        if row["failed"] != 0:
            failures.append(f"{row['failed']} failed queries — a dead "
                            f"replica must requeue, never fail")
        if row["compiles"] != len(BUCKETS):
            failures.append(f"compiles per replica process "
                            f"{row['compiles']} != {len(BUCKETS)} — "
                            f"the catalogue or the fleet broke the "
                            f"one-compile-per-(bucket, dtype) pin")
        if row["swaps"] < args.serveReplicas:
            failures.append(f"only {row['swaps']}/{args.serveReplicas} "
                            f"replicas observed the injected catalogue "
                            f"generation")
        if row["stopped"] != "target":
            failures.append("the SIGKILLed replica was not respawned "
                            "and folded back into routing")
        if row["trace_overhead_pct"] > args.trace_bar:
            failures.append(f"tracing overhead "
                            f"{row['trace_overhead_pct']:g}% over the "
                            f"{args.trace_bar:g}% bar — the per-line "
                            f"prefix peel or the sampled stamp/emit "
                            f"path got expensive")
        if row["trace_schema_errors"]:
            failures.append(f"{row['trace_schema_errors']} schema "
                            f"violations in the sampled query_trace "
                            f"stream")
        if row["dominant_hop"] is None:
            failures.append("no sampled query_trace assembled into a "
                            "waterfall — tracing went dark under the "
                            f"1-in-{TRACE_SAMPLE} sampling")
        for msg in failures:
            print(f"serve_bench FAIL: {msg}", file=sys.stderr)
        return 1 if failures else 0

    if args.serveDtype != "f32":
        print(f"serve_bench: {args.serveDtype} A/B at d={D_Q} "
              f"(f32 model 2.5 MB vs packed "
              f"{'1.25' if args.serveDtype == 'bf16' else '0.625'} MB)",
              flush=True)
        row = measure_quant(args.serveDtype, args.duration, args.sla_ms)
        print(json.dumps(row))
        if args.row:
            with open(args.row, "w") as f:
                f.write(json.dumps(row) + "\n")
        failures = []
        if (not args.correctness_only
                and row["qps_ratio"] < args.ratio_bar):
            failures.append(f"qps_ratio {row['qps_ratio']} < "
                            f"{args.ratio_bar:g} — the packed "
                            f"{args.serveDtype} path lost its "
                            f"cache-footprint win over f32")
        if row["flips"] != 0:
            failures.append(f"{row['flips']} sign flips at |m32| > 2x "
                            f"the certified bound "
                            f"{row['margin_err_bound']:.3e} — the "
                            f"certificate understated the error")
        if row["compiles"] != EXPECTED_COMPILES_Q:
            failures.append(f"{row['compiles']} scoring compiles, "
                            f"expected {EXPECTED_COMPILES_Q} (one per "
                            f"(bucket, dtype) per scorer)")
        if row["swaps"] < 1:
            failures.append("the mid-measure hot-swap never happened")
        if row["stopped"] != "target":
            failures.append("the quantized form was not the one served "
                            "(certificate fallback fired on synthetic "
                            "calibration — seed drift?)")
        for msg in failures:
            print(f"serve_bench FAIL: {msg}", file=sys.stderr)
        return 1 if failures else 0

    ck = tempfile.mkdtemp(prefix="serve-bench-")
    print(f"serve_bench: training the {N}x{D} model to gap "
          f"{GAP_TARGET:g}", flush=True)
    w_final, rounds, gap = train_checkpoints(ck)
    print(f"serve_bench: certified at round {rounds} (gap {gap:.3e}); "
          f"serving for {args.duration:g}s x {args.threads} clients",
          flush=True)
    row = measure(ck, w_final, rounds, gap, args.duration, args.threads,
                  args.sla_ms)
    print(json.dumps(row))
    if args.row:
        with open(args.row, "w") as f:
            f.write(json.dumps(row) + "\n")
    failures = []
    if row["p99_ms"] > args.sla_ms:
        failures.append(f"p99 {row['p99_ms']}ms exceeds the "
                        f"{args.sla_ms}ms SLA — the row is queries/s AT "
                        f"p99 <= SLA")
    if row["compiles"] != len(BUCKETS):
        failures.append(f"{row['compiles']} scoring compiles for "
                        f"{len(BUCKETS)} buckets — the "
                        f"one-compile-per-bucket contract broke")
    if row["swaps"] < 1:
        failures.append("the mid-bench hot-swap never happened")
    for msg in failures:
        print(f"serve_bench FAIL: {msg}", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
