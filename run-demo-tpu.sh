#!/bin/bash
# TPU-first demo: the same problem as run-demo-local.sh, driven the way a
# TPU run should be — fast-math Pallas kernels, the whole train loop as one
# on-device while_loop (one dispatch, one host fetch), random-reshuffling
# sampling (~25% fewer comm-rounds here, ~5x at epsilon scale; the duality
# gap certificate is exact under any index stream), stopping at the
# certified 1e-4 gap instead of a fixed round budget.  Index tables are
# generated in-jit on the device (--sampling=auto).  Append --blockSize=128
# on large dense problems (H >= a few hundred) for the fused block-
# coordinate MXU kernel (no cell of the benchmark runs it yet: PERF.md
# §4), and --sigma=auto on randomly-partitioned data: the reference's sigma'=K
# aggregation bound is worst-case — auto tries K/2 (which HALVED the
# certified comm-rounds on the rcv1 config) and falls back to the safe K
# if the divergence guard fires, so a wrong guess costs ~12 evals, not
# the round budget.  Append
# --accel=on --theta=adaptive for the round-12 accelerated outer loop:
# a secant extrapolation of the dual at eval-window boundaries with a
# gap-monitored restart (the rounds themselves are unmodified CoCoA+ and
# the exact gap evaluation stays the certificate — measured 1.76x fewer
# comm rounds to the same gap on rcv1-synth at the safe σ′), plus the adaptive
# local-accuracy ladder — early rounds run H/2 inner steps, tightening
# to the full H near the target, resolved on device from the gap
# estimate (docs/DESIGN.md "Accelerated outer loop").
# A multi-class file trains one-vs-rest over ONE copy of its rows: append
# --classes=auto --accel=off --mesh=1 (and --layout=dense for dense
# features).  Ten classes ride the sublanes of the dense kernel; a dense
# file of hundreds or thousands of classes (CNN features: --numFeatures=4096,
# ILSVRC's 1,000 classes) resolves, from its shapes alone, to a block of
# 256 rows a step with the classes on the lanes — the margins, one Gram
# matrix and the update as matrix products (docs/DESIGN.md section 3f):
#   python -m cocoa_tpu.cli --trainFile=features.dat --numFeatures=4096 \
#     --numSplits=8 --lambda=1e-4 --localIterFrac=0.1 --numRounds=600 \
#     --justCoCoA=true --math=fast --deviceLoop --rng=permuted \
#     --gapTarget=1e-3 --classes=auto --layout=dense --accel=off --mesh=1
cd "$(dirname "$0")"
exec python -m cocoa_tpu.cli \
  --trainFile=data/small_train.dat \
  --testFile=data/small_test.dat \
  --numFeatures=9947 \
  --numRounds=600 \
  --localIterFrac=0.1 \
  --numSplits=4 \
  --lambda=.001 \
  --justCoCoA=true \
  --math=fast \
  --deviceLoop \
  --rng=permuted \
  --gapTarget=1e-4 \
  "$@"
