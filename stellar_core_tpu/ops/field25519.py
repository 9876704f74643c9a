"""Batched GF(2^255-19) arithmetic for TPU — the limb layer of the ed25519 kernel.

Design (TPU-first, not a port): the reference reaches libsodium's ref10
(64-bit limbs, 128-bit intermediates — src/crypto/SecretKey.cpp:428 →
crypto_sign_verify_detached).  TPUs have no 64-bit integer datapath, so this
module re-derives the arithmetic for the int32 vector unit:

- A field element is 22 little-endian limbs of 12 bits (radix 2^12), stored as
  ``int32`` in the trailing axis of an array of shape ``(..., 22)``.  22*12 =
  264 bits — a redundant representation mod p = 2^255-19.
- Limbs are *signed*: subtraction just subtracts limbs; carries use arithmetic
  (floor) shifts, which are exact for negatives in two's complement.
- Multiplication forms the 43-term schoolbook convolution.  With the
  "mul-safe" input bound |limb| <= MUL_SAFE = 9885, every convolution output
  obeys |c_k| <= 22 * MUL_SAFE^2 < 2^31, so the whole product fits int32
  with no 64-bit intermediates anywhere.
- Reduction folds limb weight 2^264 == 19*2^9 (mod p) back onto limb 0,
  interleaved with parallel "weak carry" passes that keep magnitudes bounded.

Everything is batched: ops vectorise over leading axes, so one XLA program
verifies an entire TxSetFrame's signatures (SURVEY.md §5.7: the 100k-tx batch
is this framework's "long sequence").
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

P = 2**255 - 19
RADIX = 12
BASE = 1 << RADIX  # 4096
MASK = BASE - 1
NLIMBS = 22  # 22 * 12 = 264 bits
# 2^264 = 2^9 * 2^255 == 2^9 * 19 (mod p): the fold multiplier for limb 22.
FOLD = 19 << 9  # 9728
# Mul-safety: the convolution output |c_k| = |sum_{i+j=k} a_i b_j| must stay
# below 2^31.  Carry passes leave limbs 1..21 bounded by ~BASE+130 while the
# wraparound fold can leave limb 0 as large as ~BASE+2*FOLD (~24k).  For sums
# of two such elements (M0 <= 56k, M <= 17k):
#   2*M0*M + 20*M^2  <=  2*56e3*17e3 + 20*(17e3)^2  ~  7.7e9 ... too loose;
# the *actual* post-carry bounds used below are M0 <= 28k, M <= 8.4k:
#   2*28e3*8.4e3 + 20*(8.4e3)^2 = 1.88e9 < 2^31.  All routines in this module
# preserve these bounds between carries (asserted by randomized tests).
MUL_SAFE_0 = 28000  # |limb 0|
MUL_SAFE = 8400     # |limbs 1..21|


# ---------------------------------------------------------------------------
# host-side conversions (numpy / python int) — test + constant plumbing
# ---------------------------------------------------------------------------

def int_to_limbs(v: int) -> np.ndarray:
    """Python int (taken mod p) -> canonical limb vector, host side."""
    v %= P
    out = np.zeros(NLIMBS, dtype=np.int32)
    for i in range(NLIMBS):
        out[i] = v & MASK
        v >>= RADIX
    return out


def limbs_to_int(limbs) -> int:
    """Limb vector (any redundancy, signed ok) -> python int mod p."""
    arr = np.asarray(limbs)
    v = 0
    for i in range(arr.shape[-1]):
        v += int(arr[..., i]) << (RADIX * i)
    return v % P


def zeros(shape=()) -> jnp.ndarray:
    return jnp.zeros((*shape, NLIMBS), dtype=jnp.int32)


def const(v: int, shape=()) -> jnp.ndarray:
    """Broadcast a host constant into batched limb form."""
    c = jnp.asarray(int_to_limbs(v), dtype=jnp.int32)
    return jnp.broadcast_to(c, (*shape, NLIMBS))


# ---------------------------------------------------------------------------
# carries
# ---------------------------------------------------------------------------

def _split(x):
    """floor split: x == lo + (carry << RADIX), lo in [0, MASK]."""
    carry = x >> RADIX  # arithmetic shift == floor division for int32
    lo = x - (carry << RADIX)
    return lo, carry


def _carry_pass(x, below, wrap=1):
    """One parallel carry pass: each limb's low 12 bits plus the carry out
    of the limb below it, given as `below` (x moved up one limb), times
    `wrap`."""
    lo, _ = _split(x)
    return lo + wrap * (below >> RADIX)


def _rot(x):
    """Limbs moved up one place, limb 21 wrapping round to limb 0."""
    return jnp.concatenate([x[..., -1:], x[..., :-1]], axis=-1)


# a wraparound pass's weights: limb 21's carry lands on limb 0 times FOLD
_WRAP = np.array([FOLD] + [1] * (NLIMBS - 1), dtype=np.int32)


def _wrap_passes(x, passes: int, top=None):
    """`passes` wraparound carry passes over the 22 limbs; `top`, a value
    of weight 2^264 if given, lands on limb 0 times FOLD after the first.

    A pass reads the limb below, so each is computed from rotated copies of
    the input, never from the pass before it: XLA fuses the passes, where
    it would keep each pass's output as an op of its own."""
    views = [x]  # views[j]: the value rotated up j limbs
    for _ in range(passes):
        views.append(_rot(views[-1]))
    for p in range(passes):
        views = [_carry_pass(views[j], views[j + 1], np.roll(_WRAP, j))
                 for j in range(len(views) - 1)]
        if p == 0 and top is not None:
            limb0 = np.eye(NLIMBS, dtype=np.int32)  # row j: limb 0 of view j
            views = [v + limb0[j] * (FOLD * top)
                     for j, v in enumerate(views)]
    return views[0]


def weak_carry(x: jnp.ndarray, passes: int = 2) -> jnp.ndarray:
    """Parallel carry passes on a 22-limb value; carry out of limb 21 folds
    back onto limb 0 with weight 19*2^3 (2^(12*22)=2^264 ... limb21's carry has
    weight 2^264).  Keeps the representation redundant but mul-safe.

    With input |limb| <= 2^17 the result after 2 passes has limbs in
    [-3, BASE+3] — comfortably mul-safe.
    """
    return _wrap_passes(x, passes)


def _carry_full(x: jnp.ndarray, width: int) -> jnp.ndarray:
    """Sequential left-to-right carry over `width` limbs (a lax.scan over the
    limb axis — unrolling this was a major compile-size cost since freeze()
    calls it repeatedly).  After this, limbs 0..width-2 are in [0, MASK] and
    limb width-1 holds the (possibly large / signed) remainder."""
    xs = jnp.moveaxis(x, -1, 0)  # (width, ...)

    def step(c, xi):
        s = xi + c
        cn = s >> RADIX
        return cn, s - (cn << RADIX)

    c, lo = jax.lax.scan(step, jnp.zeros_like(xs[0]), xs[: width - 1])
    out = jnp.concatenate([lo, (xs[width - 1] + c)[None]], axis=0)
    return jnp.moveaxis(out, 0, -1)


# ---------------------------------------------------------------------------
# add / sub / small multiples
# ---------------------------------------------------------------------------

def add(a, b, carry: bool = True):
    x = a + b
    return weak_carry(x) if carry else x


def sub(a, b, carry: bool = True):
    x = a - b
    return weak_carry(x) if carry else x


def mul_small(a, k: int):
    """a * k for small host constant k (|k| <= ~2^13)."""
    return weak_carry(a * jnp.int32(k))


# ---------------------------------------------------------------------------
# multiplication
# ---------------------------------------------------------------------------

def _convolve(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Schoolbook product: (..., 22) x (..., 22) -> (..., 44) int32.
    Requires mul-safe inputs.  Position 43 is always zero (kept for the carry
    pass out of position 42).

    Row i is a_i times b padded into place, and the 22 rows are summed as a
    pairwise tree: one expression, which XLA fuses into one op with the
    products inside (padding b rather than the product keeps the products
    from being written out).  Integer sums do not depend on their order, so
    the limbs are those of a plain accumulation, and no partial sum exceeds
    the bound of the full one."""
    lead = [(0, 0)] * (b.ndim - 1)
    rows = [a[..., i:i + 1] * jnp.pad(b, lead + [(i, NLIMBS - i)])
            for i in range(NLIMBS)]
    while len(rows) > 1:
        pairs = [rows[k] + rows[k + 1] for k in range(0, len(rows) - 1, 2)]
        rows = pairs + rows[2 * len(pairs):]
    return rows[0]


def _reduce_product(c: jnp.ndarray) -> jnp.ndarray:
    """(..., 44) convolution -> (..., 22) mul-safe field element.

    Stage 1: two parallel carry passes over a 46-wide array (2 slack slots so
    no carry is ever dropped) bring |limb| from <2^31 to <= BASE+130.
    Stage 2: fold positions 22..44 onto 0..22 with weight FOLD
    (2^(12k) == FOLD * 2^(12(k-22)) mod p); magnitudes <= ~2^25.4.
    Stage 3: three wraparound passes over the 22 limbs; stage 2's fold of
    position 44, of weight 2^264, lands on limb 0 in the first.

    As in `_wrap_passes`, stage 1's second pass is computed from shifted
    copies of the first pass's input, so XLA fuses the two passes."""
    lead = [(0, 0)] * (c.ndim - 1)
    c = jnp.pad(c, lead + [(0, 2)])  # width 46; positions 43..45 are zero
    up1 = jnp.pad(c[..., :-1], lead + [(1, 0)])
    up2 = jnp.pad(c[..., :-2], lead + [(2, 0)])
    c = _carry_pass(_carry_pass(c, up1), _carry_pass(up1, up2))
    x = c[..., :NLIMBS] + FOLD * c[..., NLIMBS:2 * NLIMBS]
    top = FOLD * c[..., 2 * NLIMBS:2 * NLIMBS + 1]  # weight 2^264
    return _wrap_passes(x, 3, top)


def mul(a, b):
    return _reduce_product(_convolve(a, b))


def sqr(a):
    return mul(a, a)


def _sqr_times(a, n: int):
    """a^(2^n).  Rolled into a fori_loop: the exponent chains below would
    otherwise unroll ~500 multiplies at trace time, exploding XLA compile
    time/memory (observed >5 min, >10 GB on CPU).  One compiled `sqr` body
    per call site instead."""
    if n < 4:
        for _ in range(n):
            a = sqr(a)
        return a
    return jax.lax.fori_loop(0, n, lambda _, x: sqr(x), a)


# ---------------------------------------------------------------------------
# inversion / square-root powers (ref10 addition chains, re-derived)
# ---------------------------------------------------------------------------

def _pow_250_1(z):
    """z^(2^250 - 1): the shared prefix of both exponent chains."""
    z2 = sqr(z)                       # 2
    z9 = mul(_sqr_times(z2, 2), z)    # 9
    z11 = mul(z9, z2)                 # 11
    z_5_0 = mul(sqr(z11), z9)         # 2^5 - 1
    z_10_0 = mul(_sqr_times(z_5_0, 5), z_5_0)     # 2^10 - 1
    z_20_0 = mul(_sqr_times(z_10_0, 10), z_10_0)  # 2^20 - 1
    z_40_0 = mul(_sqr_times(z_20_0, 20), z_20_0)  # 2^40 - 1
    z_50_0 = mul(_sqr_times(z_40_0, 10), z_10_0)  # 2^50 - 1
    z_100_0 = mul(_sqr_times(z_50_0, 50), z_50_0)    # 2^100 - 1
    z_200_0 = mul(_sqr_times(z_100_0, 100), z_100_0)  # 2^200 - 1
    z_250_0 = mul(_sqr_times(z_200_0, 50), z_50_0)    # 2^250 - 1
    return z_250_0, z11


def inv(z):
    """z^(p-2) = z^(2^255 - 21): multiplicative inverse (0 -> 0)."""
    z_250_0, z11 = _pow_250_1(z)
    return mul(_sqr_times(z_250_0, 5), z11)


def pow22523(z):
    """z^((p-5)/8) = z^(2^252 - 3): the square-root helper exponent."""
    z_250_0, _ = _pow_250_1(z)
    return mul(_sqr_times(z_250_0, 2), z)


# ---------------------------------------------------------------------------
# canonical form / encode / decode
# ---------------------------------------------------------------------------

# p * 2^12 in limb form: added before freezing so any mul-safe negative input
# becomes a nonnegative value of the same residue (|value| < 2^266 < p*2^12).
_P_SHIFT_LIMBS = None


def _p_shift() -> np.ndarray:
    # cached as a *numpy* array: caching a jnp array created during a jit
    # trace would leak a tracer into later traces
    global _P_SHIFT_LIMBS
    if _P_SHIFT_LIMBS is None:
        v = P << RADIX
        out = np.zeros(NLIMBS + 1, dtype=np.int64)
        for i in range(NLIMBS + 1):
            out[i] = v & MASK
            v >>= RADIX
        assert v == 0
        limbs = out[:NLIMBS].astype(np.int32)
        # bits 264.. of p*2^12 live above limb 21; fold them on (19*2^9 rule):
        hi = (P << RADIX) >> (RADIX * NLIMBS)
        limbs[0] += hi * FOLD
        _P_SHIFT_LIMBS = limbs
    return _P_SHIFT_LIMBS


def freeze(a: jnp.ndarray) -> jnp.ndarray:
    """Fully-reduced canonical limbs in [0, MASK], value in [0, p).

    Accepts any mul-safe input (signed limbs allowed)."""
    x = a + _p_shift()  # nonnegative value, |limb| < 2^26
    x = weak_carry(x, passes=2)          # limbs in [-3, BASE+3], value >= 0
    x = _carry_full(x, NLIMBS)           # canonical except top limb
    # top limb may exceed 12 bits (value up to ~2^267); fold bits >= 2^264
    for _ in range(2):
        top_hi = x[..., 21] >> RADIX
        x = x.at[..., 21].add(-(top_hi << RADIX))
        x = x.at[..., 0].add(top_hi * FOLD)
        x = _carry_full(x, NLIMBS)
    # now 0 <= value < 2^264; fold bits >= 2^255 (limb 21 bits >= 3)
    for _ in range(2):
        hi = x[..., 21] >> 3
        x = x.at[..., 21].add(-(hi << 3))
        x = x.at[..., 0].add(hi * 19)
        x = _carry_full(x, NLIMBS)
    # 0 <= value < 2^255 + eps; subtract p once iff value >= p:
    # t = value + 19; value >= p  <=>  t >= 2^255  <=>  bit 3 of t's limb 21.
    t = x.at[..., 0].add(19)
    t = _carry_full(t, NLIMBS)
    ge = (t[..., 21] >> 3) > 0
    t_mod = t.at[..., 21].set(t[..., 21] & 7)
    return jnp.where(ge[..., None], t_mod, x)


def eq(a, b) -> jnp.ndarray:
    """Constant-shape equality mod p -> bool (...,)."""
    return jnp.all(freeze(a) == freeze(b), axis=-1)


def is_zero(a) -> jnp.ndarray:
    return jnp.all(freeze(a) == 0, axis=-1)


# bit <-> limb matrices (built once, host side)
_BITS_TO_LIMBS = None  # (256, 22): limb_j = sum_b bit_b * 2^(b-12j)
_PARITY = None


def _bits_to_limbs_mat() -> np.ndarray:
    # numpy, not jnp: see _p_shift tracer-leak note
    global _BITS_TO_LIMBS
    if _BITS_TO_LIMBS is None:
        m = np.zeros((256, NLIMBS), dtype=np.int32)
        for b in range(256):
            m[b, b // RADIX] = 1 << (b % RADIX)
        _BITS_TO_LIMBS = m
    return _BITS_TO_LIMBS


def bytes_to_bits(b: jnp.ndarray) -> jnp.ndarray:
    """(..., K) uint8 -> (..., 8K) int32 bits, little-endian within bytes."""
    b = b.astype(jnp.int32)
    shifts = jnp.arange(8, dtype=jnp.int32)
    bits = (b[..., :, None] >> shifts) & 1
    return bits.reshape(*b.shape[:-1], b.shape[-1] * 8)


def bits_to_bytes(bits: jnp.ndarray) -> jnp.ndarray:
    """(..., 8K) {0,1} int32 -> (..., K) uint8."""
    k = bits.shape[-1] // 8
    b = bits.reshape(*bits.shape[:-1], k, 8)
    weights = (1 << jnp.arange(8, dtype=jnp.int32))
    return jnp.sum(b * weights, axis=-1).astype(jnp.uint8)


def from_bytes(b: jnp.ndarray) -> jnp.ndarray:
    """(..., 32) uint8 little-endian -> limbs.  All 256 bits are used
    (callers mask bit 255 themselves when decoding point encodings)."""
    bits = bytes_to_bits(b)
    return bits @ _bits_to_limbs_mat()


def to_bytes(a: jnp.ndarray) -> jnp.ndarray:
    """limbs -> canonical (..., 32) uint8 little-endian."""
    x = freeze(a)
    shifts = jnp.arange(RADIX, dtype=jnp.int32)
    bits = ((x[..., :, None] >> shifts) & 1).reshape(*x.shape[:-1],
                                                     NLIMBS * RADIX)
    return bits_to_bytes(bits[..., :256])


def parity(a: jnp.ndarray) -> jnp.ndarray:
    """Low bit of the canonical value (the 'sign' in point encodings)."""
    return freeze(a)[..., 0] & 1
