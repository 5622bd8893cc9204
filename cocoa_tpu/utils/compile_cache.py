"""Persistent XLA compilation cache.

A first compile costs seconds to minutes per executable (the sparse
Pallas kernel most of all) and every entry point pays a dozen of them —
compile time, not compute, dominates a cold run's wall clock.  jax's
persistent compilation cache removes that cost across PROCESSES; the
cache key covers the HLO, compile flags, and backend, so correctness is
jax's contract, not ours.

Where the cache lives is decided outside the program when the caller
wants to: with ``JAX_COMPILATION_CACHE_DIR`` set, jax reads the variable
itself and this module sets no directory in code.  With it unset the
cache sits at ONE fixed path inside the checkout (:data:`DEFAULT_DIR`,
git-ignored) — never a temp dir, a pid or a timestamp: a directory that
moves never hits.

Enabled by the CLI, chipbench/run.py and every process chip_smoke.py
starts; set ``COCOA_NO_COMPILE_CACHE=1`` to
opt out (e.g. when measuring compile time itself).
"""

from __future__ import annotations

import os

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable() -> str | None:
    """Enable the persistent compilation cache (idempotent).  Returns the
    cache directory, or None when disabled via COCOA_NO_COMPILE_CACHE."""
    if os.environ.get("COCOA_NO_COMPILE_CACHE"):
        return None
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache everything: the suite's executables are exactly the small-once
    # big-often mix the default thresholds would skip
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir
