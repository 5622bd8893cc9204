"""Random sampling, with a reference-faithful mode.

The reference draws local example indices with ``new scala.util.Random(seed)``
(CoCoA.scala:144,151), where the per-round seed is ``debug.seed + t``
(CoCoA.scala:45) and — crucially — **every shard uses the same seed in the same
round**, so index draws are correlated across workers.  ``scala.util.Random``
delegates to ``java.util.Random``, whose 48-bit LCG is fixed by spec, so we can
reproduce the exact index sequences here without a JVM.

Two modes (selected by ``RunConfig.rng``):

- ``reference``: host-side ``JavaRandom`` precomputes the (T, H) index table,
  identical across shards — bit-faithful to the Scala behavior.  Index draws
  are data-independent (uniform), so precomputing them does not change the
  algorithm; it just moves RNG off the device hot path.
- ``jax``: ``jax.random`` keyed by (seed, round) and folded per shard —
  decorrelated across workers, the statistically preferable mode.
"""

from __future__ import annotations

import numpy as np

_MULT = 0x5DEECE66D
_ADD = 0xB
_MASK = (1 << 48) - 1


class JavaRandom:
    """Bit-exact java.util.Random (the engine behind scala.util.Random).

    Implements the linear congruential generator specified in the Java SE
    docs: seed' = (seed * 0x5DEECE66D + 0xB) mod 2^48.
    """

    def __init__(self, seed: int):
        self._seed = (seed ^ _MULT) & _MASK

    def _next(self, bits: int) -> int:
        self._seed = (self._seed * _MULT + _ADD) & _MASK
        # top `bits` bits, as a signed 32-bit int when bits == 32
        val = self._seed >> (48 - bits)
        if bits == 32 and val >= (1 << 31):
            val -= 1 << 32
        return val

    def next_int(self, bound: int | None = None) -> int:
        if bound is None:
            return self._next(32)
        if bound <= 0:
            raise ValueError("bound must be positive")
        if (bound & -bound) == bound:  # power of two
            return (bound * self._next(31)) >> 31
        while True:
            bits = self._next(31)
            val = bits % bound
            if bits - val + (bound - 1) < (1 << 31):  # no int32 overflow
                return val

    def next_double(self) -> float:
        return ((self._next(26) << 27) + self._next(27)) * (2.0 ** -53)


# ---- vectorized LCG (numpy uint64, 48-bit multiplies done in 24-bit
# halves so nothing overflows 64 bits) ----

_U_MULT = np.uint64(_MULT)
_U_ADD = np.uint64(_ADD)
_U_MASK = np.uint64(_MASK)
_LO24 = np.uint64((1 << 24) - 1)
_S24 = np.uint64(24)
_S17 = np.uint64(17)  # 48 - 31: top 31 bits for next(31)


def _scramble(seeds: np.ndarray) -> np.ndarray:
    return (seeds.astype(np.uint64) ^ _U_MULT) & _U_MASK


def _mulmod48(a, b):
    """a·b mod 2^48 elementwise, without overflowing uint64."""
    lo_a, hi_a = a & _LO24, a >> _S24
    lo_b, hi_b = b & _LO24, b >> _S24
    cross = ((lo_a * hi_b + hi_a * lo_b) & _LO24) << _S24
    return (lo_a * lo_b + cross) & _U_MASK


def _advance(states: np.ndarray) -> np.ndarray:
    return (_mulmod48(states, _U_MULT) + _U_ADD) & _U_MASK


def _state_sequence(s0: np.ndarray, m: int) -> np.ndarray:
    """seq[..., i] = advance^i(s0) for i in [0, m) — generated by jump
    doubling into a preallocated buffer, O(m) total work in O(log m) numpy
    calls (no per-draw Python loop, no reallocation copies).  advance^L is
    affine, x -> A_L·x + C_L mod 2^48, and composes as A_2L = A_L²,
    C_2L = A_L·C_L + C_L."""
    seq = np.empty(s0.shape + (m,), np.uint64)
    seq[..., 0] = s0
    a_l = np.uint64(_MULT)
    c_l = np.uint64(_ADD)
    filled = 1
    while filled < m:
        take = min(filled, m - filled)
        src = seq[..., :take]
        dst = seq[..., filled:filled + take]
        # dst = (a_l·src + c_l) mod 2^48 with in-place passes (a_l < 2^48,
        # split 24/24 so nothing overflows uint64)
        np.right_shift(src, _S24, out=dst)
        np.multiply(dst, a_l & _LO24, out=dst)      # lo_a·hi_b  (≤ 2^48)
        tmp = np.bitwise_and(src, _LO24)
        tmp *= a_l >> _S24                          # hi_a·lo_b  (≤ 2^48)
        dst += tmp
        np.bitwise_and(dst, _LO24, out=dst)
        np.left_shift(dst, _S24, out=dst)           # cross term
        np.bitwise_and(src, _LO24, out=tmp)
        tmp *= a_l & _LO24                          # lo_a·lo_b  (≤ 2^48)
        dst += tmp
        dst += c_l
        np.bitwise_and(dst, _U_MASK, out=dst)
        c_l = (_mulmod48(a_l, c_l) + c_l) & _U_MASK
        a_l = _mulmod48(a_l, a_l)
        filled += take
    return seq


def _sample_block(t0: np.ndarray, h: int, n_locals: np.ndarray) -> np.ndarray:
    """(K, len(t0), h) nextInt(n_local) tables, bit-exact with the scalar
    spec.  Key fact making this loop-free: nextInt's modulo-rejection test
    depends only on the drawn 31-bit value (reject iff bits >= the largest
    multiple of bound below 2^31), so the accepted draws are simply the
    filtered subsequence of the raw next(31) stream."""
    k, r = n_locals.shape[0], t0.shape[0]
    is_pow2 = (n_locals & -n_locals) == n_locals
    limit = ((1 << 31) // n_locals) * n_locals  # accepted iff bits < limit
    # oversample: rejection prob per draw is (2^31 - limit)/2^31 (can be
    # ~25% for bounds just above a power of two — size for the worst lane)
    p_max = float(np.max(np.where(is_pow2, 0.0, 1.0 - limit / float(1 << 31))))
    m = h
    if p_max > 0.0:
        exp = h * p_max / (1.0 - p_max)
        m = h + max(64, int(2.0 * exp + 10.0 * np.sqrt(exp)))

    s1 = _advance(np.broadcast_to(_scramble(t0)[None, :], (k, r)).copy())
    while True:
        bits = (_state_sequence(s1, m) >> _S17).astype(np.int64)
        ok = is_pow2[:, None, None] | (bits < limit[:, None, None])
        short = np.argwhere(ok.sum(axis=-1) < h)
        if short.size == 0:
            break
        m *= 2  # astronomically rare with the sizing above

    bounds = n_locals[:, None, None]
    # nominal decode: assume no rejections among the first h draws...
    vals = np.where(
        is_pow2[:, None, None],
        (bounds * bits[..., :h]) >> np.int64(31),
        bits[..., :h] % bounds,
    )
    # ...then patch the (rare) lanes that did reject, by compacting their
    # accepted subsequence (pow2 lanes never reject)
    for ki, ri in np.argwhere(np.any(~ok[..., :h], axis=-1)):
        pos = np.flatnonzero(ok[ki, ri])[:h]
        vals[ki, ri] = bits[ki, ri, pos] % n_locals[ki]
    return vals.astype(np.int32)


# ---- device-side (jnp) replay ----
#
# The reference draws its indices INSIDE each partition's task
# (CoCoA.scala:144,151); the TPU-native analogue is to draw them inside the
# jitted chunk, on device.  Host-built tables cost an h2d copy and a
# dispatch dependency every round (~0.16 MB/round of index tables at
# epsilon scale) — more than a millisecond-scale fused kernel round.
# Generating on device moves ~nothing (a (C,) vector of
# round numbers) and is bit-exact with the host tables (validated in
# tests/test_device_sampling.py).
#
# TPU has no 64-bit integers, so the 48-bit LCG runs on 12-bit limbs in
# int32: products are ≤ (2^12-1)^2 ≈ 2^24 and a column accumulates at most
# 4 products + a carry < 2^26 — comfortably inside int32.  The jump-doubling
# schedule is the same as the numpy path; its affine coefficients are exact
# Python ints computed at trace time.

_N_LIMBS = 4
_LIMB_BITS = 12
_LIMB_MASK = (1 << _LIMB_BITS) - 1


def _limbs_const(v: int):
    """A 48-bit Python int as 4 static 12-bit limbs."""
    return [(v >> (_LIMB_BITS * i)) & _LIMB_MASK for i in range(_N_LIMBS)]


def _dev_limbs(v):
    """Traced int32 (non-negative, < 2^31) -> (..., 4) 12-bit limbs."""
    import jax.numpy as jnp

    return jnp.stack(
        [(v >> (_LIMB_BITS * i)) & _LIMB_MASK for i in range(_N_LIMBS)],
        axis=-1,
    ).astype(jnp.int32)


def _dev_affine(limbs, a: int, c: int):
    """(s·a + c) mod 2^48 elementwise on (..., 4) 12-bit limb arrays;
    ``a``/``c`` are exact Python ints (static)."""
    import jax.numpy as jnp

    a_l, c_l = _limbs_const(a), _limbs_const(c)
    s = [limbs[..., i] for i in range(_N_LIMBS)]
    out, carry = [], None
    for col in range(_N_LIMBS):
        acc = None
        for i in range(col + 1):
            if a_l[col - i]:
                p = s[i] * a_l[col - i]
                acc = p if acc is None else acc + p
        if c_l[col]:
            acc = c_l[col] if acc is None else acc + c_l[col]
        if carry is not None:
            acc = carry if acc is None else acc + carry
        if acc is None:
            acc = jnp.zeros_like(s[0])
        out.append(acc & _LIMB_MASK)
        carry = acc >> _LIMB_BITS
    return jnp.stack(out, axis=-1)


def _dev_state_sequence(s0_limbs, m: int):
    """seq[..., i, :] = advance^i(s0) for i in [0, m) — jump doubling, with
    the step coefficients carried as exact Python ints (cf. the numpy
    :func:`_state_sequence`)."""
    import jax.numpy as jnp

    seq = s0_limbs[..., None, :]
    a_l, c_l = _MULT, _ADD
    filled = 1
    while filled < m:
        take = min(filled, m - filled)
        nxt = _dev_affine(seq[..., :take, :], a_l, c_l)
        seq = jnp.concatenate([seq, nxt], axis=-2)
        c_l = (a_l * c_l + c_l) & _MASK
        a_l = (a_l * a_l) & _MASK
        filled += take
    return seq


def device_sample_per_shard(seed: int, ts, h: int, n_locals) -> "object":
    """Traceable reference-mode sampling: (C, K, H) int32 tables for the
    (traced) round-number vector ``ts``, bit-exact with
    :func:`sample_indices_per_shard` (same raw next(31) stream, same
    modulo-rejection filtering).  ``seed``, ``h`` and ``n_locals`` are
    static; requires 0 <= seed + t < 2^31 for every round (callers guard via
    :func:`device_replay_ok` and fall back to host tables otherwise)."""
    import jax
    import jax.numpy as jnp

    n_locals = np.asarray(n_locals, dtype=np.int64)
    if np.any(n_locals <= 0):
        raise ValueError(f"all shards must be non-empty, got sizes {n_locals}")
    k = n_locals.shape[0]
    is_pow2 = (n_locals & -n_locals) == n_locals
    limit = ((1 << 31) // n_locals) * n_locals
    p_max = float(np.max(np.where(is_pow2, 0.0, 1.0 - limit / float(1 << 31))))
    m = h
    if p_max > 0.0:
        # the numpy path retries on shortfall; in-jit cannot, so size the
        # overdraw more generously (P(shortfall) < e^-100 by Chernoff)
        exp = h * p_max / (1.0 - p_max)
        m = h + max(64, int(3.0 * exp + 12.0 * np.sqrt(exp) + 10.0))

    # per-round state: advance(scramble(seed + t)); XOR distributes over
    # the disjoint limb bit ranges
    s0 = _dev_limbs(jnp.asarray(ts, jnp.int32) + jnp.int32(seed))
    mult_l = jnp.asarray(_limbs_const(_MULT), jnp.int32)
    s1 = _dev_affine(s0 ^ mult_l, _MULT, _ADD)
    seq = _dev_state_sequence(s1, m)                   # (C, m, 4)
    # bits = state >> 17, assembled from limbs (fits int32: < 2^31)
    bits = (
        (seq[..., 1] >> 5) + (seq[..., 2] << 7) + (seq[..., 3] << 19)
    )                                                   # (C, m)

    # Per-shard decode with SCALAR divisors/shifts: by constants division,
    # modulo and shift strength-reduce and cost nothing, where the
    # non-scalar forms measured badly on the round-4 device path (ROADMAP
    # D9 re-probes that on the chip).  K is small and static, so a Python
    # loop is free.
    vals_l, ok_l = [], []
    for s in range(k):
        cnt = int(n_locals[s])
        if is_pow2[s]:
            vals_l.append(bits >> int(31 - round(np.log2(max(cnt, 1)))))
            ok_l.append(jnp.ones_like(bits, dtype=bool))
        else:
            vals_l.append(bits % cnt)
            ok_l.append(bits < int(limit[s]))
    vals = jnp.stack(vals_l)                            # (K, C, m)
    ok = jnp.stack(ok_l)

    if not bool(np.any(~is_pow2)):
        out = vals[..., :h]
    else:
        def compact(_):
            # positions of the first h accepted draws per lane: a stable
            # argsort that orders accepted draws first, in stream order —
            # exact for any rejection pattern (jnp.searchsorted is another
            # device-path degrader; sorts are safe and fast)
            iot = jax.lax.broadcasted_iota(jnp.int32, ok.shape, ok.ndim - 1)
            keys = jnp.where(ok, iot, iot + jnp.int32(m))
            pos = jnp.argsort(keys, axis=-1)[..., :h].astype(jnp.int32)
            return jnp.take_along_axis(vals, pos, axis=-1)

        # rejections are rare at real bounds — skip the sort entirely for
        # chunks whose first h draws all accepted
        out = jax.lax.cond(
            jnp.any(~ok[..., :h]), compact, lambda _: vals[..., :h], None
        )
    return jnp.swapaxes(out, 0, 1)                      # (C, K, H)


def device_replay_ok(seed: int, max_round: int) -> bool:
    """Whether the device replay's int32 round-seed arithmetic is exact for
    this run (the host path handles the full java long range)."""
    return 0 <= seed and seed + max_round < (1 << 31)


# ---- counter-based generation for the non-reference modes ----
#
# The obvious building block — jax.random keyed per (round, shard) — is
# catastrophically slow through this device path: any jitted function that
# folds or consumes BATCHED typed PRNG keys costs ~100 ms per dispatch
# (measured round 4; single-key draws are fine, vmap-over-keys is not, and
# jax.random.permutation costs ~75 ms alone).  Both non-reference modes
# therefore generate from a stateless int32 counter hash — the same design
# philosophy as counter-based PRNGs (Salmon et al., SC'11), built from the
# SplitMix/murmur3 finalizer.  Quality is ample for index sampling, and the
# streams are deterministic functions of (seed, round, shard, position), so
# host-stepped and in-jit tables agree bitwise by construction.

_P1, _P2, _P3 = 0x9E3779B9, 0x85EBCA6B, 0xC2B2AE35  # golden/murmur constants


def _mix32(x):
    """murmur3 finalizer on uint32 — a full-avalanche 32-bit mixer."""
    import jax.numpy as jnp

    x = x.astype(jnp.uint32)
    x = (x ^ (x >> 16)) * jnp.uint32(_P2)
    x = (x ^ (x >> 13)) * jnp.uint32(_P3)
    return x ^ (x >> 16)


def hash_tables(seed: int, ts, h: int, n_locals) -> "object":
    """Traceable ``rng=jax``-mode tables: (C, K, H) int32 draws, uniform in
    [0, n_local_k), decorrelated across shards AND rounds (unlike the
    reference's shared-seed quirk).  Stateless counter hash — see the
    module note for why this replaces jax.random here; the mode's contract
    is decorrelation, not any particular stream."""
    import jax.numpy as jnp

    n_locals = np.asarray(n_locals, dtype=np.int64)
    if np.any(n_locals <= 0):
        raise ValueError(f"all shards must be non-empty, got sizes {n_locals}")
    k = n_locals.shape[0]
    ts_u = jnp.asarray(ts, jnp.int32).astype(jnp.uint32)
    s_u = jnp.arange(k, dtype=jnp.uint32)
    i_u = jnp.arange(h, dtype=jnp.uint32)
    base = _mix32(
        ts_u[:, None, None] * jnp.uint32(_P1)
        ^ (s_u[None, :, None] + jnp.uint32(0x632BE5AB))
        ^ jnp.uint32(seed & 0xFFFFFFFF)
    )
    v = (_mix32(base ^ (i_u[None, None, :] * jnp.uint32(_P3)))
         >> 1).astype(jnp.int32)
    # top 31 bits mod bound (bias ~bound/2^31 < 1e-4 — the statistical
    # mode does not replay anyone's rejection loop).  Scalar divisor per
    # shard: mod-by-array degrades this device path (see
    # device_sample_per_shard).
    return jnp.stack(
        [v[:, s, :] % int(n_locals[s]) for s in range(k)], axis=1
    )


def _feistel_perm(i, cnt: int, rk):
    """Keyed bijection on [0, cnt): a 4-round Feistel network on the
    enclosing power-of-two domain + cycle-walking back into range.  ``i``
    is a uint32 array of in-range positions; ``rk`` broadcasts with it
    (per-element round keys — each element may sit in a different epoch).
    Exact permutation: Feistel rounds are bijective on 2^b and
    cycle-walking composes bijectively onto [0, cnt); the walk loop runs
    until every element lands (expected <2 steps; P dominates cnt by at
    most 4x)."""
    import jax.numpy as jnp
    from jax import lax

    if cnt <= 1:
        return jnp.zeros_like(i)
    b = max(2, -(-int(np.ceil(np.log2(cnt))) // 2) * 2)  # even #bits
    hb = b // 2
    mask = jnp.uint32((1 << hb) - 1)

    def enc(x):
        left = x >> hb
        right = x & mask
        for r in range(4):
            f = _mix32(right * jnp.uint32(_P1) ^ rk
                       ^ jnp.uint32((r * _P2) & 0xFFFFFFFF)) & mask
            left, right = right, left ^ f
        return (left << hb) | right

    cnt_u = jnp.uint32(cnt)
    y = enc(i)
    # two unrolled walks cover almost everything; the while_loop mops up
    # the stragglers exactly (data-dependent trip count, terminates a.s.)
    y = jnp.where(y >= cnt_u, enc(y), y)
    y = jnp.where(y >= cnt_u, enc(y), y)
    y = lax.while_loop(
        lambda y: jnp.any(y >= cnt_u),
        lambda y: jnp.where(y >= cnt_u, enc(y), y),
        y,
    )
    return y


def permuted_tables(seed: int, ts, h: int, n_locals) -> "object":
    """Traceable ``rng=permuted``-mode tables: (C, K, H) int32 random-
    reshuffling draws.  Shard s's draws form one continuous stream across
    rounds — global step g = (t-1)·H + j reads perm_{(s, g // n_s)}[g mod
    n_s], where perm is the keyed Feistel bijection above, evaluated
    POSITIONALLY (no O(n) permutation ever materializes, no sorts — the
    per-epoch key is a per-element hash of (seed, shard, epoch), so epoch
    boundaries mid-round cost nothing).  Every epoch covers every
    coordinate exactly once; chunking-invariant and resumable because only
    (seed, shard, epoch) key the bijection."""
    import jax.numpy as jnp

    n_locals = np.asarray(n_locals, dtype=np.int64)
    if np.any(n_locals <= 0):
        raise ValueError(f"all shards must be non-empty, got sizes {n_locals}")
    ts = jnp.asarray(ts, jnp.int32)
    c = int(ts.shape[0])
    g = (ts[0] - 1) * jnp.int32(h) + jnp.arange(c * h, dtype=jnp.int32)
    seed_u = jnp.uint32(seed & 0xFFFFFFFF)
    outs = []
    for s in range(n_locals.shape[0]):
        cnt = int(n_locals[s])
        e = (g // cnt).astype(jnp.uint32)           # per-element epoch
        pos = (g % cnt).astype(jnp.uint32)
        rk = _mix32(e * jnp.uint32(_P3)
                    ^ jnp.uint32((s + 1) * _P1 & 0xFFFFFFFF) ^ seed_u)
        idx = _feistel_perm(pos, cnt, rk)
        outs.append(idx.astype(jnp.int32).reshape(c, h))
    return jnp.stack(outs, axis=1)  # (C, K, H)


def sample_indices(seed: int, rounds: range, h: int, n_local: int) -> np.ndarray:
    """Index table for the reference RNG mode.

    For round t the reference seeds ``Random(seed + t)`` and draws H indices
    uniform in [0, n_local) (CoCoA.scala:148-151).  Returns int32 array of
    shape (len(rounds), H).  All shards share this table (the reference's
    correlated-across-workers behavior); callers wanting per-shard tables pass
    a shard-adjusted seed.  Vectorized over rounds (rounds reseed
    independently, so their LCG streams are independent lanes).
    """
    return sample_indices_per_shard(seed, rounds, h, [n_local])[0]


def sample_indices_per_shard(
    seed: int, rounds: range, h: int, n_locals: "list[int] | np.ndarray"
) -> np.ndarray:
    """Reference-mode index table for K shards of (possibly) unequal size.

    Shard k replays ``Random(seed + t)`` against its own ``n_local`` — exactly
    what each Spark task does with its partition (CoCoA.scala:144,151).  Shape
    (K, len(rounds), H).  Equal-size shards see identical draws (the
    reference's correlated-across-workers behavior).
    """
    n_locals = np.asarray(n_locals, dtype=np.int64)
    if np.any(n_locals <= 0):
        raise ValueError(f"all shards must be non-empty, got sizes {n_locals}")
    t0 = np.asarray([seed + t for t in rounds], dtype=np.int64)
    k, r = n_locals.shape[0], len(t0)
    out = np.empty((k, r, h), dtype=np.int32)
    # block over rounds so the (K, block, ~h) uint64 working set stays small
    block = max(1, 4_000_000 // max(1, k * h))
    for lo in range(0, r, block):
        out[:, lo:lo + block] = _sample_block(t0[lo:lo + block], h, n_locals)
    return out
