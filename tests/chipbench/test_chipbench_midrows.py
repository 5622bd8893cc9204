"""The mid-row cell's own pieces, on the CPU: the harness finds everything
``url.cocoa_plus`` names; the configuration is the whole published set
(``reduced: []``) and states every guess; the job is webspam's but for its
check and the two numbers its check takes from it; the cell owes its
metrics by name and each has a reader; the long-row generator at url's arguments makes rows whose lengths send a file
to the stream under the program's own rule; the plain reference on those
rows agrees with the program, and the check passes a float32 pair and fails
the same w rounded once to bfloat16; the two byte counts equal hand counts.

Tolerances as tests/chipbench/test_chipbench_longrows.py: the reference
against the program's float32 objectives (the stream kernels in interpret
mode) 2e-6 relative, the gap 5e-6."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import (cost_model_sparse, cost_model_stream,  # noqa: E402
                       reference_longrows, registry)

from owed import check_cell  # noqa: E402

BENCH = registry.load_benchmark(ROOT)
CELL = "url.cocoa_plus"
SMALL = dict(name="small", n=768, d=32768, num_splits=4,
             local_iter_frac=0.1, dtype="float32", loss="hinge",
             layout="sparse", mean_nnz=115.6,
             generator_args=dict(max_nnz=512, mean_nnz=115.6, sigma_nnz=0.5,
                                 flip=0.02, planted_hot_cut=256))
SMALL["lambda"] = 1e-3
# the two entries only this cell reads (readers of its own)
BLOCK = ["midrow_eval_roofline", "midrow_nonzero_ns"]
# the scope readings it shares with other cells, one entry each (PR 55)
SCOPES = ["local_solve_ms", "eval_share", "accel_jump_share",
          "unscoped_share", "sparse_solve_roofline"]
# peak_hbm_gb, setup_s and a job's fixed part by part: the dense cells'
# accounts (chipbench/readers/cold_account.py, fixed_part.py), read in
# this cell under the accounts' own entries
ACCOUNTS = {name: moves for moves, names in {
    "peak_hbm_gb": ["hbm_entry_gb", "hbm_rise_layout_gb", "hbm_rise_job_gb",
                    "hbm_rise_after_gb", "hbm_resident_gb",
                    "hbm_program_temp_gb"],
    "setup_s": ["cold_layout_s", "cold_build_s", "cold_job_s"],
    "job_s": ["fixed_init_s", "fixed_stage_s", "fixed_dispatch_s",
              "fixed_fetch_s", "fixed_unspanned_s"],
}.items() for name in names}
SHARED = SCOPES + list(ACCOUNTS)
GENERIC = ["device_idle_share", "fixed_s", "launches_per_job", "round_ms",
           "top_op_share", "compile_s", "compiles_in_window"]


@pytest.fixture(scope="module")
def gen():
    mod = registry.load_module(BENCH, "generators", "longrows_zipf")
    mod.WINDOW = 1 << 14        # a shard of this size spans two windows
    return mod


@pytest.fixture(scope="module")
def small(gen):
    """``gen.make`` with the pre-flight answered by a resolver that has a
    solve for every size (this process's platform is cpu, where the
    program's own answer is ``fori``)."""
    real = gen.preflight
    gen.preflight = lambda config, resolve=None: {}
    try:
        return gen.make(SMALL, 4100000029)
    finally:
        gen.preflight = real


def test_the_harness_resolves_the_cell():
    cell = registry.resolve_cell(BENCH, CELL)
    cfg, job = cell["config"], cell["job"]
    assert (cell["chips"], cfg["name"], cfg["layout"]) == (1, "url", "sparse")
    assert cfg["generator"] == "longrows_zipf"
    assert job["check"] == "certified_gap_midrows"
    # webspam's job to the letter, but for its check
    webspam = registry.resolve_cell(BENCH, "webspam.cocoa_plus")["job"]
    for key in ("entry", "flags", "params", "debug", "kwargs", "stop",
                "expect_path"):
        assert job[key] == webspam[key]
    assert not {"pallas", "block_size", "hot_cols"} & set(job["kwargs"])
    gen = registry.load_module(BENCH, "generators", cfg["generator"])
    check = registry.load_module(BENCH, "checks", job["check"])
    assert callable(gen.make) and callable(gen.preflight)
    assert callable(check.audit) and callable(check.job_problem)
    # the check's two numbers are the job's, each with its argument
    assert set(job["audit"]) == {"w_tol", "block_slots"}
    assert 1e-5 < job["audit"]["w_tol"] < 2e-3  # under one bf16 rounding of w
    assert job["audit"]["block_slots"] == 1 << 20
    for word in ("w_tol:", "block_slots:", "w_err_bf16", "compile"):
        assert word in job["audit_why"]


def test_the_configuration_is_the_whole_published_set():
    cfg = registry.resolve_cell(BENCH, CELL)["config"]
    (entry,) = [c for c in BENCH["configs"] if c["name"] == "url"]
    assert entry["reduced"] == cfg["reduced"] == []
    assert entry["source"] == cfg["source"] and "#url" in cfg["source"]
    assert cfg["architecture"] is None          # a deployment, not a model
    assert (cfg["n"], cfg["d"], cfg["num_splits"]) == (2396130, 3231961, 8)
    assert cfg["mean_nnz"] == cfg["generator_args"]["mean_nnz"] == 115.6
    assert cfg["generator_args"]["max_nnz"] == 512
    assert cfg["generator_args"]["sigma_nnz"] == 0.5
    assert (cfg["dtype"], cfg["loss"], cfg["local_iter_frac"]) == (
        "float32", "hinge", 0.1)
    assert int(cfg["local_iter_frac"] * cfg["n"] / 8) == 29951
    # the label law and flips are kddb's
    kddb = registry.resolve_cell(BENCH, "kddb.cocoa_plus")["config"]
    for key in ("flip", "planted_density_inv", "planted_hot_cut"):
        assert cfg["generator_args"][key] == kddb["generator_args"][key]
    assert cfg["lambda"] in (1e-5, 1e-4)        # ISSUE 41's sizing rule (3)
    text = json.dumps(cfg["assumed"])
    for word in ("stand-in", "512", "lambda", "K = 8", "remembered",
                 "log-normal", "Zipf", "test split", "sigma 0.5"):
        assert word in text
    for word in ("float32", "certificate", "[0, 1]"):
        assert word in cfg["guarantees"]
    for word in ("15 s", "60 s", "1e-3", "reduced = []"):
        assert word in cfg["sizing_rule"]
    assert "1611.02189" in cfg["what"]


@pytest.mark.parametrize("name", BLOCK + SHARED + GENERIC)
def test_a_traced_line_of_the_cell_can_carry_the_metric(name):
    readers = {m["name"]: (m, read, params) for m, read, params
               in registry.layer_readers(BENCH, CELL)}
    m, read, params = readers[name]
    assert callable(read)
    assert m["moves"] == ACCOUNTS.get(
        name, "setup_s" if name == "compile_s" else "job_s")
    if name in BLOCK:
        assert m["workloads"] == [CELL]
    elif name in SHARED:
        assert CELL in m.get("workloads", [CELL])
    else:
        assert "workloads" not in m
    want = {"local_solve_ms": {"scope": "cocoa_local_solve",
                               "per_round": True},
            "eval_share": {"scope": "cocoa_eval"},
            "accel_jump_share": {"scope": "cocoa_accel_jump"},
            "unscoped_share": {"scope": None}}
    if name in want:
        assert params == want[name]
    if name in ACCOUNTS:
        module = read.__module__.rsplit("_readers_", 1)[-1]
        assert module == ("fixed_part" if name.startswith("fixed_")
                          else "cold_account")


def test_the_cell_owes_these_metrics():
    check_cell(BENCH, CELL, BLOCK, GENERIC, SHARED)
    # and no other cell owes an entry of its block
    for w in BENCH["workloads"]:
        if w["name"] != CELL:
            assert not set(BLOCK) & {m["name"] for m in
                                     registry.metrics_of(
                                         BENCH, "per_layer", w["name"])}


def test_the_accounts_read_nothing_where_the_program_keeps_none():
    """A tree without cold records or ``cocoa/`` spans: nothing, and no
    error (the dense cells' readers, unedited)."""
    read, params = registry.layer_reader(BENCH, "hbm_resident_gb")
    assert read(None, [], {"cold_account": None}, **params) is None
    assert read(None, [], {"cold_account": {"hbm_resident_gb": 3.4}},
                **params) == 3.4


def test_the_byte_counts_equal_hand_counts():
    cfg = registry.resolve_cell(BENCH, CELL)["config"]
    # a pass: 8 B a nonzero, 20 B a row, w once
    assert cost_model_stream.pass_bytes(1000, 500, 100.0) == (
        8 * 100000 + 20 * 1000 + 4 * 500)
    whole = cost_model_stream.pass_bytes(cfg["n"], cfg["d"], cfg["mean_nnz"])
    assert whole == pytest.approx(2.2767e9, rel=1e-3)   # 2.8 ms at 819 GB/s
    # a round: the long-row cell's floor at these sizes
    model = cost_model_sparse.sparse_round(8, 29951, 115.6)
    assert model["steps"] == 239608
    assert model["hbm_bytes"] == pytest.approx(
        239608 * 115.6 * 20 + 239608 * 16)
    floor_s = registry.load_module(BENCH, "readers",
                                   "sparse_solve_roofline").floor_s
    cell = {**registry.resolve_cell(BENCH, CELL), "local_iters": 29951,
            "device_kind": "TPU v5 lite",
            "solver_path": {"inner": "sequential", "kernel": "pallas",
                            "storage": "stream"}}
    assert floor_s(cell) == pytest.approx(model["hbm_bytes"] / 819e9)
    # the readers find nothing off the stream's Pallas path
    read, _ = registry.layer_reader(BENCH, "midrow_eval_roofline")
    assert read(None, [], {**cell, "solver_path": {"kernel": "fori"}}) is None
    read, _ = registry.layer_reader(BENCH, "midrow_nonzero_ns")
    assert read(None, [], {**cell, "config": {"n": 1}}) is None


def test_the_generators_lengths_answer_the_rule(gen, small):
    """The benchmark tied to the program's rule: rows drawn by the
    configuration's law (log-normal, sigma 0.5, mean 115.6, clipped at
    512) make ``stream_suits`` answer stream, at the law's own quantiles
    and not only in this sample; and a chunk of the kernels' ring holds
    all but a few of them whole."""
    from cocoa_tpu.data.sharding import stream_suits
    from cocoa_tpu.ops.pallas_longrows import CHUNK, chunk_fill

    lens = np.asarray(small.sp_row_len)[np.asarray(small.mask) > 0]
    assert lens.size == 768 and 1 <= lens.min() and lens.max() <= 512
    assert abs(lens.mean() - 115.6) < 8
    assert abs(np.log(lens[lens < 512]).std() - 0.5) < 0.05
    assert stream_suits(lens)
    assert lens.max() + 120 <= CHUNK    # a row is one chunk wherever it starts
    assert 0.08 < chunk_fill(small.sp_row_ptr, small.sp_row_len) < 0.14
    # the law at the published n: quantiles of the log-normal, one row at
    # the clip (0.06% of 2.4 million rows reach it)
    cfg = registry.resolve_cell(BENCH, CELL)["config"]
    args = cfg["generator_args"]
    mu = gen.length_mu(args["mean_nnz"], args["sigma_nnz"], args["max_nnz"])
    assert abs(mu - (np.log(115.6) - 0.125)) < 0.01
    from statistics import NormalDist

    q = (np.arange(20000) + 0.5) / 20000
    law = np.clip(np.round(np.exp(mu + args["sigma_nnz"] * np.array(
        [NormalDist().inv_cdf(x) for x in q]))), 1, args["max_nnz"])
    assert abs(law.mean() - 115.6) < 0.5 and law.max() == 512
    assert stream_suits(law)
    # the rectangle these rows would take, against the stream
    assert 512 * law.size > 4 * (-(-law // 8) * 8).sum()


def test_the_check_passes_float32_and_fails_a_bf16_w(gen, small):
    """A (w, alpha) with w = w(alpha) in float32 passes the check's
    comparison of w; the same w rounded once to bfloat16 fails it; and the
    reference agrees with the program's objectives on these rows."""
    import jax.numpy as jnp

    from cocoa_tpu.evals import objectives

    ds, lam = small, SMALL["lambda"]
    check = registry.load_module(BENCH, "checks", "certified_gap_midrows")
    r = np.random.RandomState(0)
    y, mask = np.asarray(ds.labels), np.asarray(ds.mask)
    alpha = (r.rand(*y.shape) * mask).astype(np.float32)
    cols = np.asarray(ds.sp_indices).reshape(ds.k, -1)
    vals = np.asarray(ds.sp_values).reshape(ds.k, -1)
    ptr, length = np.asarray(ds.sp_row_ptr), np.asarray(ds.sp_row_len)
    wsum = np.zeros(SMALL["d"])
    for a in range(ds.k):
        for i in range(int(ds.counts[a])):
            at = 8 * ptr[a, i]
            c = cols[a, at:at + length[a, i]]
            wsum[c] += (vals[a, at:at + length[a, i]].astype(np.float64)
                        * float(y[a, i]) * float(alpha[a, i]))
    w = (wsum / (lam * ds.n)).astype(np.float32)
    out = reference_longrows.recompute(ds, jnp.asarray(w),
                                       jnp.asarray(alpha), lam)
    job = registry.resolve_cell(BENCH, CELL)["job"]
    limit = job["audit"]["w_tol"] * max(1.0, out["w_scale"])
    assert out["w_err"] <= 1e-6 * out["w_scale"] < limit
    assert out["w_err_bf16"] > limit and out["stray_values"] == 0
    p, g, _ = objectives.evaluate(ds, jnp.asarray(w), jnp.asarray(alpha),
                                  lam)
    assert p == pytest.approx(out["primal"], rel=2e-6)
    assert g == pytest.approx(out["gap"], rel=5e-6)

    # the audit itself, on a job's record: float32 passes, bf16 fails by
    # the comparison of w
    class Rec:
        round, primal, gap = 5, out["primal"], out["gap"]

    class Traj:
        records, stopped = [Rec], "target"

    cell = {"job": {"stop": {"rule": "certified_gap",
                             "target": out["gap"] * 1.01,
                             "round_budget": 300},
                    "audit": job["audit"]},
            "config": {"lambda": lam, "loss": "hinge"}}
    run = dict(w=jnp.asarray(w), alpha=jnp.asarray(alpha), traj=Traj,
               rounds=5, wall_s=1.0)
    good = check.audit(cell, ds, run)
    assert good["ok"] and good["w_limit"] == limit
    rounded = jnp.asarray(w).astype(jnp.bfloat16).astype(jnp.float32)
    bad = check.audit(cell, ds, {**run, "w": rounded})
    assert not bad["ok"]
    assert any("w != (1/(lam n))" in p for p in bad["problems"])
    # a limit so wide that a bfloat16 w passes it is itself a problem
    wide = {**cell, "job": {**cell["job"], "audit": {
        **job["audit"], "w_tol": 1e-2}}}
    loose = check.audit(wide, ds, run)
    assert not loose["ok"]
    assert any("passes a bfloat16 w" in p for p in loose["problems"])
