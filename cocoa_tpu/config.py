"""Algorithm and run configuration.

Mirrors the reference's core datatypes (OptClasses.scala:21-29 ``Params``,
OptClasses.scala:38-42 ``DebugParams``) and the full CLI flag inventory
(hingeDriver.scala:22-38), as plain dataclasses.  The loss is selected by name
rather than by function pointer so configs stay serializable and jit-friendly.
"""

from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass
class Params:
    """Algorithmic parameters (reference: OptClasses.scala:21-29).

    Notation follows the CoCoA papers: K = number of shards/workers,
    H = ``local_iters`` local steps per round, T = ``num_rounds``.
    """

    n: int                      # global number of training examples
    num_rounds: int = 200       # T, outer iterations (hingeDriver.scala:33)
    local_iters: int = 1        # H, local steps per round (hingeDriver.scala:70-71)
    lam: float = 0.01           # lambda, L2 regularization (hingeDriver.scala:32)
    beta: float = 1.0           # update scaling; 1 = averaging (hingeDriver.scala:35)
    gamma: float = 1.0          # CoCoA+ aggregation; 1 = adding (hingeDriver.scala:36)
    loss: str = "hinge"         # "hinge" | "smooth_hinge" | "logistic" (extension)
    smoothing: float = 1.0      # smooth_hinge smoothing parameter s (unused
                                # by the other losses)
    sigma: Optional[float] = None  # σ′ subproblem-coupling override (extension;
                                # None = the reference's safe bound K·γ,
                                # CoCoA.scala:45; the string "auto" =
                                # start at the aggressive K·γ/2 and back
                                # off toward K·γ when the stall watch
                                # fires — in place on the device by
                                # default (--sigmaSchedule=anneal), or
                                # via the trial-then-rerun A/B control
                                # (--sigmaSchedule=trial) —
                                # solvers/cocoa.run_cocoa).  K·γ assumes worst-case
                                # cross-shard coherence; random shards
                                # tolerate less — measured on the rcv1
                                # config, σ′=K/2 HALVES the certified
                                # comm-rounds to the 1e-4 gap while
                                # anything below K/2 diverges (σ′=3.5 at
                                # K=8 already does — which the exact
                                # duality-gap certificate reports rather
                                # than hides)


@dataclasses.dataclass
class DebugParams:
    """Systems/debugging parameters (reference: OptClasses.scala:38-42)."""

    debug_iter: int = 10        # evaluate every this many rounds; <=0 disables
    seed: int = 0
    chkpt_iter: int = 201       # checkpoint every this many rounds (num_rounds+1 disables)
    chkpt_dir: str = ""         # empty disables checkpointing (hingeDriver.scala:55-59)


@dataclasses.dataclass
class RunConfig:
    """Full run configuration = the reference CLI flag set (hingeDriver.scala:22-38)
    plus TPU-specific knobs that have no Spark analogue."""

    # --- reference flags (names kept 1:1 so the CLI is drop-in) ---
    train_file: str = ""
    test_file: str = ""
    num_features: int = 0
    num_splits: int = 1          # K, number of data shards (= mesh size by default)
    chkpt_dir: str = ""
    chkpt_iter: int = 100
    just_cocoa: bool = True
    lam: float = 0.01            # --lambda
    num_rounds: int = 200
    local_iter_frac: float = 1.0
    beta: float = 1.0
    gamma: float = 1.0
    debug_iter: int = 10
    seed: int = 0

    # --- TPU-native knobs (no reference analogue) ---
    dtype: str = "float32"       # compute dtype; reference is float64 throughout
    layout: str = "auto"         # "dense" | "sparse" (padded-CSR) | "auto"
    rng: str = "reference"       # "reference": java.util.Random, one seed shared by
                                 #   all shards per round (CoCoA.scala:45,144);
                                 # "jax": jax PRNG folded per (round, shard) —
                                 #   decorrelated across shards (improvement);
                                 # "permuted": random reshuffling — per-shard
                                 #   per-epoch permutations, every coordinate
                                 #   once per epoch (~5x fewer comm-rounds to
                                 #   the certified gap at epsilon scale)
    sampling: str = "auto"       # where index tables are generated:
                                 # "auto" (in-jit on device whenever exact —
                                 # the production default: an h2d table copy
                                 # per round costs more than the round),
                                 # "device",
                                 # or "host" (concrete tables, debug path)
    scan_chunk: int = 0          # >0: run rounds device-side in lax.scan blocks
                                 # of this size (one dispatch per block)
    math: str = "exact"          # "exact": reference-order float ops (bit-
                                 #   matchable vs the oracle in x64);
                                 # "fast": margins decomposition — one MXU
                                 #   matvec per round + incremental Δw dots,
                                 #   auto-Pallas inner loop on TPU (CoCoA only)
    device_loop: bool = False    # run the whole train loop (incl. gap-target
                                 # early stop) as one on-device while_loop
    mesh_shape: Optional[tuple] = None  # (dp,) or (dp, fp); None = (num_splits,)
    loss: str = "hinge"
    smoothing: float = 1.0
    sigma: "float | str" = 0.0   # σ′ override (0 = the safe K·γ default;
                                 # a float, or "auto"); see Params.sigma

    def to_params(self, n: int, k: int) -> Params:
        """H = max(1, localIterFrac * n / K) as in hingeDriver.scala:70-71."""
        h = max(1, int(self.local_iter_frac * n / k))
        return Params(
            n=n,
            num_rounds=self.num_rounds,
            local_iters=h,
            lam=self.lam,
            beta=self.beta,
            gamma=self.gamma,
            loss=self.loss,
            smoothing=self.smoothing,
            sigma=("auto" if self.sigma == "auto"
                   else self.sigma if self.sigma > 0 else None),
        )

    def to_debug(self, num_rounds: Optional[int] = None) -> DebugParams:
        rounds = self.num_rounds if num_rounds is None else num_rounds
        chkpt_iter = self.chkpt_iter if self.chkpt_dir else rounds + 1
        return DebugParams(
            debug_iter=self.debug_iter,
            seed=self.seed,
            chkpt_iter=chkpt_iter,
            chkpt_dir=self.chkpt_dir,
        )


# Mapping from reference CLI flag names (hingeDriver.scala:22-38) to RunConfig
# field names.  "master" is not here: the CLI consumes it as a run-level flag
# (it selects local vs multi-host mode, cli.py).
REFERENCE_FLAGS = {
    "trainFile": "train_file",
    "testFile": "test_file",
    "numFeatures": "num_features",
    "numSplits": "num_splits",
    "chkptDir": "chkpt_dir",
    "chkptIter": "chkpt_iter",
    "justCoCoA": "just_cocoa",
    "lambda": "lam",
    "numRounds": "num_rounds",
    "localIterFrac": "local_iter_frac",
    "beta": "beta",
    "gamma": "gamma",
    "debugIter": "debug_iter",
    "seed": "seed",
}
