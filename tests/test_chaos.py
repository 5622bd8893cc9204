"""Chaos suite: every recovery path under deterministic fault injection.

The fault harness (tests/_faults.py) drives the elastic supervisor's
hooks with scheduled kills / wedges / checkpoint corruption, so each
failure mode the supervisor claims to survive is pinned by a
reproducible test:

- shrink-to-survivors: a worker lost mid-run reforms the gang at P′ < P
  and the run completes bit-identically to an unfailed control.  The
  real-process toy-gang pair rides the slow marker purely for tier-1
  wall-clock budget (it runs on ANY jax — ``-m slow -k gang_`` — and in
  the CI chaos step, which also runs tests/chaos_smoke.py end to end);
  the real-TRAINING 2-process pin is additionally gated on a jax with
  multi-process CPU collectives like the rest of the repo's gang tests;
- checkpoint generations: a torn newest checkpoint falls back to the
  previous generation (validation-on-load), and the resumed run still
  reproduces the uninterrupted trajectory exactly;
- bounded KV ops: a peer that never publishes fails in bounded time
  with the peer/key named, not a silent 10-minute hang;
- restart backoff: exponential with seeded jitter, capped, reset on
  progress.
"""

import json
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _faults import (Fault, FaultPlan, checkpoint_at_least, sigkill,
                     truncate_newest_checkpoint)
from cocoa_tpu import checkpoint as ckpt_lib
from cocoa_tpu import elastic
from cocoa_tpu.parallel import distributed
from cocoa_tpu.telemetry import events as tele_events
from cocoa_tpu.telemetry import schema as tele_schema

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TESTS = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(autouse=True)
def clean_bus():
    tele_events.get_bus().reset()
    yield tele_events.get_bus()
    tele_events.get_bus().reset()


# --- unit: the shrink arithmetic and backoff policy --------------------------


def test_shrink_gang_size_math():
    # largest P' < P whose device count divides K, one device per worker
    assert elastic.shrink_gang_size(8, 4) == 2  # 3 does not divide 8
    assert elastic.shrink_gang_size(8, 2) == 1
    assert elastic.shrink_gang_size(6, 4) == 3
    assert elastic.shrink_gang_size(5, 2) == 1  # K % 1 == 0 always
    assert elastic.shrink_gang_size(4, 1) is None  # nothing below 1
    # multi-device workers can genuinely strand a K
    assert elastic.shrink_gang_size(6, 2, devices_per_worker=4) is None
    assert elastic.shrink_gang_size(8, 2, devices_per_worker=4) == 1
    assert elastic.shrink_gang_size(16, 4, devices_per_worker=4) == 2


def test_backoff_growth_cap_and_determinism():
    import random

    # jitter 0: pure capped doubling
    rng = random.Random(0)
    seq = [elastic.backoff_seconds(s, 1.0, 8.0, 0.0, rng)
           for s in range(1, 7)]
    assert seq == [1.0, 2.0, 4.0, 8.0, 8.0, 8.0]
    # base <= 0 disables; streak 0 never waits
    assert elastic.backoff_seconds(3, 0.0, 8.0, 0.5, rng) == 0.0
    assert elastic.backoff_seconds(0, 1.0, 8.0, 0.5, rng) == 0.0
    # jittered values stay inside [1-j, 1+j] x the capped delay, and the
    # seeded stream is reproducible (deterministic chaos runs)
    a = [elastic.backoff_seconds(s, 0.5, 4.0, 0.5, random.Random(7))
         for s in range(1, 5)]
    b = [elastic.backoff_seconds(s, 0.5, 4.0, 0.5, random.Random(7))
         for s in range(1, 5)]
    assert a == b
    for s, v in enumerate(a, start=1):
        d = min(4.0, 0.5 * 2 ** (s - 1))
        assert 0.5 * d <= v <= 1.5 * d


class _DeadProc:
    """A worker that is already dead with exit code 3."""

    def __init__(self, spawned):
        spawned.append(self)

    def poll(self):
        return 3

    def send_signal(self, sig):
        pass

    def wait(self, timeout=None):
        return 3


def _dead_spawner(sizes):
    spawned = []

    def spawn(worker_argv, i, n, port, python, module, quiet_tail, resume):
        if i == 0:
            sizes.append(n)
        return _DeadProc(spawned)
    return spawn


def test_supervise_shrinks_after_budget(monkeypatch):
    """--elastic=N default: same-size restarts until max_restarts
    consecutive failures, then reform at P' instead of giving up; give up
    only when even the 1-worker gang burns its budget."""
    sizes = []
    restarts = []
    monkeypatch.setattr(elastic, "_spawn", _dead_spawner(sizes))
    rc = elastic.supervise(
        [], 4, max_restarts=1, poll_s=0.0, resume=False,
        num_splits=8, shrink="auto", backoff_base_s=0.0,
        on_restart=lambda gen, reason, old, new, backoff:
            restarts.append((old, new)),
    )
    assert rc == 3
    # 4,4 (budget burns) -> 2,2 (8 % 3 != 0, so 4 shrinks to 2) -> 1,1
    assert sizes == [4, 4, 2, 2, 1, 1]
    assert (4, 2) in restarts and (2, 1) in restarts


def test_supervise_shrinks_immediately(monkeypatch):
    """shrink="now" (--elastic=shrink): the first loss at each size
    reforms the gang — no same-size retries on the way down."""
    sizes = []
    monkeypatch.setattr(elastic, "_spawn", _dead_spawner(sizes))
    rc = elastic.supervise(
        [], 4, max_restarts=1, poll_s=0.0, resume=False,
        num_splits=8, shrink="now", backoff_base_s=0.0,
    )
    assert rc == 3
    assert sizes == [4, 2, 1, 1]  # 1-worker gang still gets its budget


def test_supervise_shrink_now_spares_stalled_gang(monkeypatch):
    """A STALL has every process alive (transient wedge), so shrink="now"
    must not downsize on it: stalls burn the restart budget like before,
    and shrink fires only when the budget exhausts."""
    sizes = []

    class Wedged:
        def poll(self):
            return None

        def send_signal(self, sig):
            pass

        def wait(self, timeout=None):
            return -9

    def spawn(worker_argv, i, n, port, python, module, quiet_tail, resume):
        if i == 0:
            sizes.append(n)
        return Wedged()

    monkeypatch.setattr(elastic, "_spawn", spawn)
    rc = elastic.supervise(
        [], 2, max_restarts=1, poll_s=0.0, resume=False,
        num_splits=4, shrink="now", backoff_base_s=0.0,
        progress_token=lambda: 42, stall_timeout_s=0.01,
    )
    assert rc == 1
    # first stall: same-size restart (no immediate shrink); second stall
    # exhausts the budget -> shrink to 1; then the 1-gang burns its own
    assert sizes == [2, 2, 1, 1]


def test_supervise_shrink_rejects_non_divisor(monkeypatch, capsys):
    """No smaller gang's devices divide K -> loud give-up, not a crash
    loop (4-chip workers, K=6: 1 worker = 4 devices, 6 % 4 != 0)."""
    sizes = []
    monkeypatch.setattr(elastic, "_spawn", _dead_spawner(sizes))
    rc = elastic.supervise(
        [], 2, max_restarts=0, poll_s=0.0, resume=False,
        num_splits=6, shrink="now", devices_per_worker=4,
        backoff_base_s=0.0,
    )
    assert rc == 3
    assert sizes == [2]  # never relaunched
    err = capsys.readouterr().err
    assert "cannot reform the gang" in err and "numSplits=6" in err


def test_supervise_shrink_strips_explicit_mesh(monkeypatch):
    """A user --mesh pins the OLD device grid; the reformed gang drops it
    and re-infers from P' (same-size generations keep it)."""
    lines = []

    def spawn(worker_argv, i, n, port, python, module, quiet_tail, resume):
        lines.append((n, list(worker_argv)))
        return _DeadProc([])

    monkeypatch.setattr(elastic, "_spawn", spawn)
    elastic.supervise(
        ["--mesh=4", "--lambda=.01"], 4, max_restarts=0, poll_s=0.0,
        resume=False, num_splits=8, shrink="now", backoff_base_s=0.0,
    )
    by_size = {n: argv for n, argv in lines}
    assert "--mesh=4" in by_size[4]
    assert "--mesh=4" not in by_size[2] and "--lambda=.01" in by_size[2]


def test_supervise_emits_gang_resize_and_schema_valid(monkeypatch,
                                                      tmp_path):
    """The typed gang_resize / restart events land in the JSONL and pass
    the schema checker like every other dialect."""
    ev = tmp_path / "events.jsonl"
    tele_events.get_bus().configure(jsonl_path=str(ev))
    monkeypatch.setattr(elastic, "_spawn", _dead_spawner([]))
    elastic.supervise(
        [], 4, max_restarts=0, poll_s=0.0, resume=False,
        num_splits=8, shrink="auto", backoff_base_s=0.0,
    )
    assert tele_schema.check_file(str(ev)) == []
    recs = [json.loads(ln) for ln in ev.read_text().splitlines()]
    resizes = [r for r in recs if r["event"] == "gang_resize"]
    assert [(r["old_size"], r["new_size"]) for r in resizes] == [(4, 2),
                                                                 (2, 1)]
    restarts = [r for r in recs if r["event"] == "restart"]
    assert restarts and all("gang_size" in r and "backoff_s" in r
                            for r in restarts)
    # a resize must still report the attempts that exhausted the budget,
    # never "attempt 0" (the counter resets AFTER the event)
    assert all(r["attempt"] >= 1 for r in restarts)


def test_supervise_dumps_victim_flightrec(monkeypatch, tmp_path):
    """A worker death with --events configured leaves a `.flightrec`
    explanation artifact: the supervisor tails the victim's stream
    (telemetry/recorder.dump_victim) before deciding the restart.  Dead
    worker 0 here, so the victim stream is the shared events file — the
    pre-seeded worker events must be what the dump carries."""
    ev = tmp_path / "events.jsonl"
    with open(ev, "w") as f:
        for t in (5, 10):
            f.write(json.dumps(
                {"event": "checkpoint_write", "seq": t, "pid": 777,
                 "ts": float(t), "algorithm": "ToyGang", "round": t,
                 "path": "x"}) + "\n")
    tele_events.get_bus().configure(jsonl_path=str(ev))
    monkeypatch.setattr(elastic, "_spawn", _dead_spawner([]))
    elastic.supervise([], 2, max_restarts=0, poll_s=0.0, resume=False,
                      num_splits=4, shrink="now", backoff_base_s=0.0)
    path = str(ev) + ".flightrec"
    assert os.path.exists(path)
    assert tele_schema.check_file(path) == []
    recs = [json.loads(ln) for ln in open(path)]
    man = recs[0]["flightrec_manifest"]
    assert man["reason"] == "worker_died" and man["source"] == "supervisor"
    assert man["victim_index"] == 0 and man["exit_code"] == 3
    # _DeadProc has no pid to scope by — the dump is the stream's
    # last-known state, and says so
    assert man["scope"] == "stream"
    assert any(r.get("event") == "checkpoint_write" and r["pid"] == 777
               for r in recs[1:])


def test_metrics_writer_gang_gauges(tmp_path):
    """gang_resize / restart / checkpoint_corrupt events drive the new
    gauges and counters; the gang families render as a dedicated subset
    so the supervisor's sibling `<metrics>.gang` file never duplicates
    worker series (textfile collectors reject duplicate families)."""
    from cocoa_tpu.telemetry.metrics import MetricsWriter

    path = tmp_path / "m.prom"
    w = MetricsWriter(str(path))
    # a worker that never sees gang events must not render gang families
    assert "cocoa_gang" not in path.read_text()
    base = {"seq": 1, "ts": 0.0, "pid": 1}
    w({**base, "event": "restart", "reason": "worker_died", "attempt": 1,
       "generation": 1, "gang_size": 4, "backoff_s": 1.5})
    w({**base, "event": "gang_resize", "reason": "worker_died",
       "old_size": 4, "new_size": 2, "generation": 2})
    w({**base, "event": "checkpoint_corrupt", "algorithm": "CoCoA+",
       "path": "x.npz", "reason": "torn"})
    text = path.read_text()
    assert "cocoa_gang_size 2" in text
    assert "cocoa_gang_generations_total 3" in text
    assert "cocoa_restart_backoff_seconds 1.5" in text
    assert "cocoa_checkpoint_corrupt_total 1" in text

    # the supervisor's gang-only writer: gang families and NOTHING else
    gpath = tmp_path / "m.prom.gang"
    g = MetricsWriter(str(gpath), families="gang")
    g({**base, "event": "gang_resize", "reason": "worker_died",
       "old_size": 2, "new_size": 1, "generation": 1})
    gtext = gpath.read_text()
    assert "cocoa_gang_size 1" in gtext
    assert "cocoa_gang_generations_total 2" in gtext
    assert "cocoa_rounds_total" not in gtext
    assert "cocoa_restarts_total" not in gtext
    with pytest.raises(ValueError, match="families"):
        MetricsWriter(str(gpath), families="nope")


# --- CLI flag surface --------------------------------------------------------


def _cli_spy(monkeypatch):
    calls = {}

    def spy(worker_argv, n_workers, **kw):
        calls["argv"] = worker_argv
        calls["n"] = n_workers
        calls.update(kw)
        return 0

    monkeypatch.setattr("cocoa_tpu.elastic.supervise", spy)
    return calls


BASE_FLAGS = ["--trainFile=x.dat", "--numFeatures=10", "--numSplits=4"]


def test_cli_elastic_shrink_specs(monkeypatch):
    from cocoa_tpu import cli

    calls = _cli_spy(monkeypatch)
    assert cli.main(BASE_FLAGS + ["--elastic=2"]) == 0
    assert calls["n"] == 2 and calls["shrink"] == "auto"
    assert calls["num_splits"] == 4

    calls = _cli_spy(monkeypatch)
    assert cli.main(BASE_FLAGS + ["--elastic=2,shrink"]) == 0
    assert calls["n"] == 2 and calls["shrink"] == "now"

    calls = _cli_spy(monkeypatch)
    assert cli.main(BASE_FLAGS + ["--elastic=shrink",
                                  "--numProcesses=3"]) == 0
    assert calls["n"] == 3 and calls["shrink"] == "now"

    # multi-chip workers declare their device count so shrink sizes
    # against DEVICES, not processes (it can never be probed — the
    # supervisor must not initialize a backend its workers need)
    calls = _cli_spy(monkeypatch)
    assert cli.main(BASE_FLAGS + ["--elastic=2,shrink,devices=4"]) == 0
    assert calls["n"] == 2 and calls["shrink"] == "now"
    assert calls["devices_per_worker"] == 4


def test_cli_elastic_shrink_rejections(monkeypatch, capsys):
    from cocoa_tpu import cli

    _cli_spy(monkeypatch)
    # bare shrink with no gang size anywhere
    assert cli.main(BASE_FLAGS + ["--elastic=shrink"]) == 2
    assert "gang size" in capsys.readouterr().err
    # junk spec
    assert cli.main(BASE_FLAGS + ["--elastic=two"]) == 2
    capsys.readouterr()
    # devices= must be a positive integer
    assert cli.main(BASE_FLAGS + ["--elastic=2,devices=0"]) == 2
    assert cli.main(BASE_FLAGS + ["--elastic=2,devices=x"]) == 2
    capsys.readouterr()
    # fp gang cannot shrink: explicit ask rejected loudly...
    assert cli.main(BASE_FLAGS + ["--elastic=2,shrink", "--fp=2"]) == 2
    assert "feature-parallel" in capsys.readouterr().err
    # ...the default degrades to same-size supervision with a note
    calls = _cli_spy(monkeypatch)
    assert cli.main(BASE_FLAGS + ["--elastic=2", "--fp=2"]) == 0
    assert calls["shrink"] == "off"
    assert "same-size restarts" in capsys.readouterr().err


# --- checkpoint generations + validation ------------------------------------


def _save_rounds(directory, rounds, alg="CoCoA+", d=8, k=2, n=4):
    rng = np.random.default_rng(0)
    for t in rounds:
        ckpt_lib.save(str(directory), alg, t,
                      jnp.asarray(rng.random(d)),
                      jnp.asarray(rng.random((k, n))), seed=0)


def test_checkpoint_keeps_two_generations(tmp_path):
    _save_rounds(tmp_path, [5, 10, 15, 20])
    paths = ckpt_lib.generations(str(tmp_path), "CoCoA+")
    assert [os.path.basename(p) for p in paths] == [
        "CoCoA+-r000015.npz", "CoCoA+-r000020.npz"]
    # sidecars pruned with their archives
    assert sorted(f for f in os.listdir(tmp_path) if f.endswith(".json")) \
        == ["CoCoA+-r000015.npz.json", "CoCoA+-r000020.npz.json"]
    # per-algorithm: another algorithm's files are never claimed
    _save_rounds(tmp_path, [5], alg="CoCoA")
    assert len(ckpt_lib.generations(str(tmp_path), "CoCoA+")) == 2
    assert len(ckpt_lib.generations(str(tmp_path), "CoCoA")) == 1


def test_checkpoint_generations_order_numerically(tmp_path):
    """Past round 999999 the 06d stamp widens: ordering must follow the
    ROUND, not the string, or pruning would delete the newest file."""
    _save_rounds(tmp_path, [999998, 999999, 1000000])
    paths = ckpt_lib.generations(str(tmp_path), "CoCoA+")
    assert [os.path.basename(p) for p in paths] == [
        "CoCoA+-r999999.npz", "CoCoA+-r1000000.npz"]
    assert ckpt_lib.latest(str(tmp_path), "CoCoA+").endswith(
        "CoCoA+-r1000000.npz")


def test_checkpoint_prune_spares_stale_higher_rounds(tmp_path):
    """A reused directory holding HIGHER-round leftovers from an earlier
    run must not make pruning eat the fresh run's own saves."""
    _save_rounds(tmp_path, [400, 500])   # the earlier run's leftovers
    _save_rounds(tmp_path, [100])        # a fresh run starts over
    names = [os.path.basename(p)
             for p in ckpt_lib.generations(str(tmp_path), "CoCoA+")]
    # the just-written r100 survives; the stale files stay untouched
    # (exactly as benign/visible as before pruning existed)
    assert names == ["CoCoA+-r000100.npz", "CoCoA+-r000400.npz",
                     "CoCoA+-r000500.npz"]


def test_checkpoint_validate_rejects_bare_npy(tmp_path):
    """A stray .npy overwriting the checkpoint makes np.load return a
    plain ndarray — validate must report it (and latest fall back), not
    crash closing a handle that has no close()."""
    _save_rounds(tmp_path, [5, 10])
    prev, newest = ckpt_lib.generations(str(tmp_path), "CoCoA+")
    np.save(open(newest, "wb"), np.zeros(3))
    assert ckpt_lib.validate(newest) == "not an npz archive"
    assert ckpt_lib.latest(str(tmp_path), "CoCoA+") == prev


def test_checkpoint_validate_catches_corruption(tmp_path):
    _save_rounds(tmp_path, [5, 10])
    good, newest = ckpt_lib.generations(str(tmp_path), "CoCoA+")
    assert ckpt_lib.validate(newest) is None
    # torn file (half-written copy)
    with open(newest, "r+b") as f:
        f.truncate(100)
    assert "unreadable" in (ckpt_lib.validate(newest) or "")
    # garbage overwrite: zip opens nothing
    with open(newest, "wb") as f:
        f.write(b"\x00" * 4096)
    assert ckpt_lib.validate(newest) is not None
    assert ckpt_lib.validate(good) is None


def test_checkpoint_validate_catches_shape_mismatch(tmp_path):
    _save_rounds(tmp_path, [5])
    (path,) = ckpt_lib.generations(str(tmp_path), "CoCoA+")
    meta, arrays = ckpt_lib.load_full(path)
    # rewrite the archive with a truncated w but the original meta: the
    # recorded shapes disagree -> rejected
    arrays["w"] = arrays["w"][:-2]
    np.savez(open(path, "wb"), _meta=np.array(json.dumps(meta)), **arrays)
    reason = ckpt_lib.validate(path)
    assert reason is not None and "shape" in reason


def test_latest_falls_back_to_previous_generation(tmp_path, clean_bus):
    seen = []
    clean_bus.subscribe(seen.append)
    _save_rounds(tmp_path, [5, 10])
    prev, newest = ckpt_lib.generations(str(tmp_path), "CoCoA+")
    with open(newest, "r+b") as f:
        f.truncate(100)
    assert ckpt_lib.latest(str(tmp_path), "CoCoA+") == prev
    corrupt = [r for r in seen if r["event"] == "checkpoint_corrupt"]
    assert len(corrupt) == 1 and corrupt[0]["path"] == newest
    # both generations torn -> None (and the caller starts from round 1,
    # which is correct, not a crash)
    with open(prev, "r+b") as f:
        f.truncate(100)
    assert ckpt_lib.latest(str(tmp_path), "CoCoA+") is None


def test_corrupt_newest_resumes_previous_bit_identical(tmp_path):
    """End to end on the real solver: tear the newest checkpoint; the
    resume falls back one generation and REPLAYS to the same final state
    bit for bit (round-keyed sampling makes the extra rounds free)."""
    from cocoa_tpu.config import DebugParams, Params
    from cocoa_tpu.data.sharding import shard_dataset
    from cocoa_tpu.data.synth import synth_sparse
    from cocoa_tpu.solvers import run_cocoa

    data = synth_sparse(64, 32, nnz_mean=6, seed=4)
    ds = shard_dataset(data, k=2, layout="dense", dtype=jnp.float64)
    p = Params(n=data.n, num_rounds=20, local_iters=8, lam=0.01)
    d = DebugParams(debug_iter=5, seed=0, chkpt_iter=5,
                    chkpt_dir=str(tmp_path))
    w_full, a_full, _ = run_cocoa(ds, p, d, plus=True, quiet=True)
    gens = ckpt_lib.generations(str(tmp_path), "CoCoA+")
    assert [os.path.basename(g) for g in gens] == [
        "CoCoA+-r000015.npz", "CoCoA+-r000020.npz"]
    with open(gens[-1], "r+b") as f:
        f.truncate(80)
    path = ckpt_lib.latest(str(tmp_path), "CoCoA+")
    assert path == gens[0]
    meta, w0, a0 = ckpt_lib.load(path)
    assert meta["round"] == 15
    w_res, a_res, _ = run_cocoa(
        ds, p, DebugParams(debug_iter=5, seed=0), plus=True, quiet=True,
        w_init=w0, alpha_init=a0, start_round=16)
    np.testing.assert_array_equal(np.asarray(w_res), np.asarray(w_full))
    np.testing.assert_array_equal(np.asarray(a_res), np.asarray(a_full))


# --- bounded KV ops ----------------------------------------------------------


class _NeverClient:
    """blocking_key_value_get that always times out (dead peer)."""

    def __init__(self):
        self.calls = 0

    def key_value_set(self, key, val):
        pass

    def blocking_key_value_get(self, key, timeout_ms):
        self.calls += 1
        time.sleep(timeout_ms / 1000.0)
        raise RuntimeError("DEADLINE_EXCEEDED: Deadline Exceeded")


class _FlakyClient:
    """Fails fast twice (transient coordinator error), then succeeds."""

    def __init__(self):
        self.calls = 0

    def blocking_key_value_get(self, key, timeout_ms):
        self.calls += 1
        if self.calls < 3:
            raise RuntimeError("UNAVAILABLE: connection reset")
        return "ok"


def test_blocking_kv_get_bounded_and_actionable(monkeypatch):
    monkeypatch.setattr(distributed, "_KV_BACKOFF_BASE_S", 0.001)
    client = _NeverClient()
    t0 = time.monotonic()
    with pytest.raises(RuntimeError) as e:
        distributed.blocking_kv_get(client, "cocoa/x/1/n",
                                    timeout_s=0.3, attempt_s=0.05,
                                    what="peer process 1, exchange 'x'")
    elapsed = time.monotonic() - t0
    assert elapsed < 5.0                      # bounded, not 600 s
    assert client.calls >= 2                  # it retried
    msg = str(e.value)
    assert "cocoa/x/1/n" in msg and "peer process 1" in msg
    assert "--elastic" in msg                 # names the remedy


def test_blocking_kv_get_no_backoff_after_slow_attempts():
    """An attempt that consumed its blocking wait was LISTENING the whole
    time — no backoff sleep after it, or the budget is spent deaf.  With
    0.3s budget / 0.05s attempts the client must be polled many times."""
    client = _NeverClient()
    with pytest.raises(RuntimeError):
        distributed.blocking_kv_get(client, "k", timeout_s=0.3,
                                    attempt_s=0.05)
    assert client.calls >= 4


def test_blocking_kv_get_retries_transient_errors(monkeypatch):
    # backoff pauses shrunk so the test is instant
    monkeypatch.setattr(distributed, "_KV_BACKOFF_BASE_S", 0.001)
    client = _FlakyClient()
    assert distributed.blocking_kv_get(client, "k", timeout_s=5.0,
                                       attempt_s=0.1) == "ok"
    assert client.calls == 3


def test_host_allgather_names_missing_peer(monkeypatch):
    client = _NeverClient()
    monkeypatch.setattr(distributed, "kv_client", lambda: client)
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(jax, "process_index", lambda: 0)
    with pytest.raises(RuntimeError, match="peer process 1"):
        distributed.host_allgather_bytes("tag0", b"payload",
                                         timeout_s=0.2, attempt_s=0.05)


# --- real-process gang: kill -> shrink -> bit-identical ----------------------


def _gang_env(monkeypatch):
    # workers must see the repo + tests on PYTHONPATH and must not
    # inherit the virtual 8-device flag (they use no devices, but keep
    # the environment identical to the real gang tests)
    monkeypatch.setenv(
        "PYTHONPATH",
        f"{ROOT}{os.pathsep}{TESTS}{os.pathsep}"
        f"{os.environ.get('PYTHONPATH', '')}")
    monkeypatch.setenv("XLA_FLAGS", " ".join(
        f for f in os.environ.get("XLA_FLAGS", "").split()
        if "host_platform_device_count" not in f))


def _toy_argv(ckdir, k=4, rounds=20, step_s=0.05):
    return [f"--chkptDir={ckdir}", f"--numSplits={k}",
            f"--numRounds={rounds}", "--chkptIter=5",
            f"--stepSeconds={step_s}"]


def _run_toy_control(ckdir, k=4, rounds=20, step_s=0.05):
    rc = elastic.supervise(_toy_argv(ckdir, k, rounds, step_s), 2,
                           module="_gang_worker", max_restarts=0,
                           poll_s=0.05, backoff_base_s=0.0)
    assert rc == 0
    return ckpt_lib.load(ckpt_lib.latest(str(ckdir), "ToyGang"))


def _tear_once_on_restart(ckdir):
    """on_restart hook: tear the newest checkpoint exactly once, AFTER
    the gang is down and BEFORE the survivors relaunch — the
    deterministic window where no writer can replace the torn file."""
    done = []

    def hook(gen, reason, old, new, backoff):
        if not done:
            truncate_newest_checkpoint(ckdir)([])
            done.append(gen)
    return hook


@pytest.mark.slow
def test_gang_sigkill_shrinks_to_survivor_bit_identical(tmp_path,
                                                        monkeypatch):
    """A REAL 2-process jax.distributed gang (the toy worker: real
    rendezvous, real KV allgather per round, real checkpoints) loses
    worker 1 to SIGKILL mid-run; the supervisor reforms at P'=1, the
    survivor resumes and completes — final state bit-identical to the
    unfailed 2-process control.  With --events on the workers, the
    SIGKILL additionally yields a validated `.flightrec` dump from the
    supervisor path carrying the victim's last-N events (the ISSUE-10
    acceptance pin)."""
    _gang_env(monkeypatch)
    ck = tmp_path / "ck"
    ev = tmp_path / "events.jsonl"
    tele_events.get_bus().configure(jsonl_path=str(ev))
    plan = FaultPlan(
        Fault(generation=0, actions=(sigkill(1),),
              trigger=checkpoint_at_least(ck, "ToyGang", 5),
              name="kill-worker-1"),
    )
    resizes = []
    # --trace as well: spans flow from round 1, so the victim's stream
    # is deterministically nonempty whenever the kill lands (checkpoint
    # events alone would race — the trigger can fire on worker 0's save
    # before worker 1 has written anything)
    rc = elastic.supervise(
        _toy_argv(ck) + [f"--events={ev}", "--trace"], 2,
        module="_gang_worker",
        max_restarts=3,
        poll_s=0.05, num_splits=4, shrink="now", backoff_base_s=0.0,
        on_generation=plan.on_generation,
        on_restart=lambda gen, reason, old, new, backoff:
            resizes.append((old, new)),
    )
    plan.join()
    assert rc == 0
    assert plan.errors == []
    assert plan.fired == ["kill-worker-1"]
    assert (2, 1) in resizes
    meta, w, _ = ckpt_lib.load(ckpt_lib.latest(str(ck), "ToyGang"))
    assert meta["round"] == 20

    # unfailed 2-process control: bit-identical final state
    meta_c, w_c, _ = _run_toy_control(tmp_path / "ref")
    assert meta_c["round"] == 20
    np.testing.assert_array_equal(w, w_c)

    # the machine-readable trace validates like every other dialect and
    # records the resize
    assert tele_schema.check_file(str(ev)) == []
    recs = [json.loads(ln) for ln in ev.read_text().splitlines()]
    assert any(r["event"] == "gang_resize" and r["new_size"] == 1
               for r in recs)

    # the crash explanation artifact: the SIGKILLed worker 1 could not
    # dump its own ring, so the supervisor tailed worker 1's stream
    # (`<events>.p1`) and dumped on its behalf — a validated flightrec
    # naming the victim and carrying its last events (the checkpoint
    # writes that were its final observable acts)
    frec = str(ev) + ".p1.flightrec"
    assert os.path.exists(frec)
    assert tele_schema.check_file(frec) == []
    frecs = [json.loads(ln) for ln in open(frec)]
    man = frecs[0]["flightrec_manifest"]
    assert man["reason"] == "worker_died"
    assert man["source"] == "supervisor" and man["victim_index"] == 1
    # a real Popen victim: the tail is scoped to the dead process's pid
    assert man["scope"] == "victim"
    victim_events = frecs[1:]
    assert victim_events, "the dump must carry the victim's events"
    assert {r["pid"] for r in victim_events} == {man["victim_pid"]}
    # worker 1 was mid-flight: its last observable acts — round spans
    # (guaranteed from round 1) and usually its round-5 checkpoint
    assert any(r["event"] == "span" for r in victim_events)


@pytest.mark.slow
def test_gang_kill_plus_torn_checkpoint_resumes_previous(tmp_path,
                                                         monkeypatch,
                                                         capfd):
    """Same loss, but the newest checkpoint is ALSO torn (the half-copied
    file a preemption leaves — injected in the on_restart window, after
    teardown and before relaunch, so no writer can race it): the survivor
    falls back one generation, replays the extra rounds, and still lands
    bit-identical to the control."""
    _gang_env(monkeypatch)
    ck = tmp_path / "ck"
    # slower rounds: the kill lands while r10 is still the newest save,
    # so the torn newest is r10 and the fallback generation is r5
    plan = FaultPlan(
        Fault(generation=0, actions=(sigkill(1),),
              trigger=checkpoint_at_least(ck, "ToyGang", 10),
              name="kill-worker-1"),
    )
    rc = elastic.supervise(
        _toy_argv(ck, step_s=0.15), 2, module="_gang_worker",
        max_restarts=3, poll_s=0.05, num_splits=4, shrink="now",
        backoff_base_s=0.0, on_generation=plan.on_generation,
        on_restart=_tear_once_on_restart(ck),
    )
    plan.join()
    assert rc == 0
    assert plan.errors == []
    assert plan.fired == ["kill-worker-1"]
    meta, w, _ = ckpt_lib.load(ckpt_lib.latest(str(ck), "ToyGang"))
    assert meta["round"] == 20
    # the survivor resumed from the PREVIOUS generation (round 5, not the
    # torn round-10 file) — worker 0 inherits stdout, so its resume line
    # is observable here
    out = capfd.readouterr().out
    assert "resuming ToyGang from round 5" in out
    meta_c, w_c, _ = _run_toy_control(tmp_path / "ref")
    np.testing.assert_array_equal(w, w_c)


# --- the real-training chaos pin (needs multi-process CPU collectives) -------


def _real_training_argv(train, ckdir, ev, rounds=200, cache_dir=None):
    argv = [
        f"--trainFile={train}", "--numFeatures=64",
        f"--numRounds={rounds}", "--localIterFrac=0.2", "--numSplits=2",
        "--lambda=.01", "--justCoCoA=true", "--debugIter=10",
        f"--chkptDir={ckdir}", "--chkptIter=10", "--dtype=float64",
        f"--events={ev}",
    ]
    if cache_dir is not None:
        argv.append(f"--ingestCache={cache_dir}")
    return argv


def _final_gaps(ev_path):
    """Last run_end gap per algorithm from an events JSONL."""
    gaps = {}
    with open(ev_path) as f:
        for line in f:
            r = json.loads(line)
            if r.get("event") == "run_end" and r.get("gap") is not None:
                gaps[r["algorithm"]] = r["gap"]
    return gaps


@pytest.mark.slow
@pytest.mark.parametrize("tear_newest", [False, True],
                         ids=["sigkill", "sigkill+torn-ckpt"])
def test_chaos_real_training_shrink_bit_identical(tmp_path, monkeypatch,
                                                  tear_newest):
    """THE chaos pin: a real 2-process localhost training gang with one
    worker SIGKILLed mid-run completes on the survivor (P'=1) and its
    final (w, alpha, gap) is bit-identical to the unfailed 2-process
    control; with the newest checkpoint also torn, the survivor resumes
    from the previous generation and the pin still holds.  The chaos arm
    rides --ingestCache (the control stays uncached — slab-cache
    bit-identity is part of what the A/B proves): the shrunken
    generation's re-ingest must be a full cache hit with ZERO re-parsed
    bytes (the ISSUE-15 shrink contract — shard artifacts are
    geometry-free, so the survivor maps its inherited shards warm)."""
    from cocoa_tpu.data.synth import synth_sparse, write_libsvm

    _gang_env(monkeypatch)
    data = synth_sparse(96, 64, nnz_mean=8, seed=2)
    train = tmp_path / "train.dat"
    write_libsvm(data, str(train))

    ck = tmp_path / "ck"
    ev = tmp_path / "events.jsonl"
    tele_events.get_bus().configure(jsonl_path=str(ev))
    plan = FaultPlan(
        Fault(generation=0, actions=(sigkill(1),),
              trigger=checkpoint_at_least(ck, "CoCoA+", 10),
              name="chaos"),
    )
    rc = elastic.supervise(
        _real_training_argv(train, ck, ev,
                            cache_dir=tmp_path / "icache"),
        2, max_restarts=3,
        num_splits=2, shrink="now", backoff_base_s=0.2,
        on_generation=plan.on_generation,
        # tearing in the on_restart window (gang down, survivors not yet
        # relaunched) is the only race-free injection point — a live
        # worker 0 could otherwise land a fresh save after the tear
        on_restart=(_tear_once_on_restart(ck) if tear_newest else None),
    )
    plan.join()
    assert rc == 0
    assert plan.errors == []
    assert plan.fired == ["chaos"]

    ck_ref = tmp_path / "ck_ref"
    ev_ref = tmp_path / "events_ref.jsonl"
    rc_ref = elastic.supervise(
        _real_training_argv(train, ck_ref, ev_ref), 2, max_restarts=0,
    )
    assert rc_ref == 0

    for alg in ("CoCoA+", "CoCoA"):
        path = ckpt_lib.latest(str(ck), alg)
        path_ref = ckpt_lib.latest(str(ck_ref), alg)
        assert path is not None and path_ref is not None
        meta, w, a = ckpt_lib.load(path)
        meta_r, w_r, a_r = ckpt_lib.load(path_ref)
        assert meta["round"] == meta_r["round"] == 200
        np.testing.assert_array_equal(w, w_r)
        np.testing.assert_array_equal(a, a_r)
    # the certified gap agrees exactly too (run_end carries it)
    assert _final_gaps(ev) == _final_gaps(ev_ref)
    assert tele_schema.check_file(str(ev)) == []
    recs = [json.loads(ln) for ln in ev.read_text().splitlines()]
    if tear_newest:
        assert any(r["event"] == "checkpoint_corrupt" for r in recs)
    # the shrink re-ingest contract: the reformed generation (the last
    # ingest on worker 0's stream, after the gang_resize) served every
    # inherited shard from the slab cache — zero re-parsed bytes
    ingests = [r for r in recs if r["event"] == "ingest"]
    assert ingests and ingests[0]["cache"] == "miss"
    assert ingests[-1]["cache"] == "hit"
    assert ingests[-1]["bytes_read"] == 0
