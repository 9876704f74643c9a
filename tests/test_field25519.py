"""Randomized + edge-case tests for the GF(2^255-19) limb layer.

Every op is checked against python-int arithmetic mod p (the same oracle role
libsodium's ref10 plays for the reference — SURVEY.md §7 "hard parts")."""
import numpy as np
import pytest

import jax.numpy as jnp

from stellar_core_tpu.ops import field25519 as F

P = F.P

EDGE = [0, 1, 2, 19, P - 1, P - 2, P - 19, 2**255 - 19 - 1, 2**252, 7]


def _rand_ints(n, rng):
    return [int.from_bytes(rng.bytes(32), "little") % P for _ in range(n)]


def _batch(vals):
    return jnp.asarray(np.stack([F.int_to_limbs(v) for v in vals]))


def test_roundtrip_int_limbs():
    rng = np.random.default_rng(1)
    for v in EDGE + _rand_ints(20, rng):
        assert F.limbs_to_int(F.int_to_limbs(v)) == v % P


def test_add_sub_mul_random():
    rng = np.random.default_rng(2)
    avals = EDGE + _rand_ints(40, rng)
    bvals = list(reversed(EDGE)) + _rand_ints(40, rng)
    bvals = bvals[: len(avals)]
    a, b = _batch(avals), _batch(bvals)
    got_add = np.asarray(F.freeze(F.add(a, b)))
    got_sub = np.asarray(F.freeze(F.sub(a, b)))
    got_mul = np.asarray(F.freeze(F.mul(a, b)))
    for i, (x, y) in enumerate(zip(avals, bvals)):
        assert F.limbs_to_int(got_add[i]) == (x + y) % P, f"add {i}"
        assert F.limbs_to_int(got_sub[i]) == (x - y) % P, f"sub {i}"
        assert F.limbs_to_int(got_mul[i]) == (x * y) % P, f"mul {i}"


def test_mul_chain_stays_safe():
    # repeated mul/add/sub chains must keep limbs in the mul-safe envelope
    rng = np.random.default_rng(3)
    vals = _rand_ints(8, rng)
    x = _batch(vals)
    ref = vals
    for step in range(30):
        x2 = F.mul(x, x)
        x = F.sub(F.add(x2, x), x2)  # == x, but exercises add/sub bounds
        x = F.mul(x, x2)
        ref = [(v * v * v) % P for v in ref]
        assert np.abs(np.asarray(x)[..., 1:]).max() <= F.MUL_SAFE
        assert np.abs(np.asarray(x)[..., 0]).max() <= F.MUL_SAFE_0
    frozen = np.asarray(F.freeze(x))
    for i, v in enumerate(ref):
        assert F.limbs_to_int(frozen[i]) == v


def test_freeze_negative_and_redundant():
    # hand-built redundant/signed limb vectors
    rng = np.random.default_rng(4)
    raws = np.stack(
        [
            np.full(F.NLIMBS, -8000, dtype=np.int32),
            np.full(F.NLIMBS, 8000, dtype=np.int32),
            np.concatenate([[27000], np.full(F.NLIMBS - 1, 8191)]).astype(np.int32),
            rng.integers(-8192, 8192, F.NLIMBS).astype(np.int32),
            np.zeros(F.NLIMBS, dtype=np.int32),
        ]
    )
    frozen = np.asarray(F.freeze(jnp.asarray(raws)))
    for i in range(raws.shape[0]):
        want = sum(int(raws[i, j]) << (12 * j) for j in range(F.NLIMBS)) % P
        assert F.limbs_to_int(frozen[i]) == want
        assert frozen[i].min() >= 0 and frozen[i].max() <= F.MASK


def test_inv_and_pow22523():
    rng = np.random.default_rng(5)
    vals = [v for v in EDGE if v != 0] + _rand_ints(10, rng)
    x = _batch(vals)
    got_inv = np.asarray(F.freeze(F.inv(x)))
    got_pow = np.asarray(F.freeze(F.pow22523(x)))
    for i, v in enumerate(vals):
        assert F.limbs_to_int(got_inv[i]) == pow(v, P - 2, P)
        assert F.limbs_to_int(got_pow[i]) == pow(v, (P - 5) // 8, P)


def test_bytes_roundtrip():
    rng = np.random.default_rng(6)
    vals = EDGE + _rand_ints(10, rng)
    b = np.stack(
        [np.frombuffer(int.to_bytes(v, 32, "little"), dtype=np.uint8) for v in vals]
    )
    limbs = F.from_bytes(jnp.asarray(b))
    for i, v in enumerate(vals):
        assert F.limbs_to_int(np.asarray(limbs)[i]) == v % P
    # to_bytes produces the canonical little-endian encoding
    out = np.asarray(F.to_bytes(limbs))
    for i, v in enumerate(vals):
        assert out[i].tobytes() == int.to_bytes(v % P, 32, "little")


def test_eq_parity():
    vals = [5, P - 5, 5, 0, 1]
    x = _batch(vals)
    y = _batch([5, 5, P - 5, 0, P - 1])
    got = np.asarray(F.eq(x, y))
    assert got.tolist() == [True, False, False, True, False]
    par = np.asarray(F.parity(_batch([2, 3, P - 1, P - 2])))
    assert par.tolist() == [0, 1, (P - 1) & 1, (P - 2) & 1]


def _mul_model(a, b):
    """F.mul spelled out in NumPy int64: the schoolbook product, then the
    reduction's three stages pass by pass, each pass written out."""
    n = a.shape[0]
    c = np.zeros((n, 2 * F.NLIMBS + 2), dtype=np.int64)
    for i in range(F.NLIMBS):
        for j in range(F.NLIMBS):
            c[:, i + j] += a[:, i] * b[:, j]
    assert np.abs(c).max() < 2**31  # the product fits int32
    for _ in range(2):
        carry = c >> F.RADIX
        c = c - (carry << F.RADIX)
        c[:, 1:] += carry[:, :-1]
    out = np.zeros((n, F.NLIMBS + 1), dtype=np.int64)
    out[:, :F.NLIMBS] = c[:, :F.NLIMBS]
    out += F.FOLD * c[:, F.NLIMBS:2 * F.NLIMBS + 1]
    for _ in range(3):
        carry = out[:, :F.NLIMBS] >> F.RADIX
        body = out[:, :F.NLIMBS] - (carry << F.RADIX)
        body[:, 1:] += carry[:, :-1]
        body[:, 0] += F.FOLD * (out[:, F.NLIMBS] + carry[:, -1])
        out = np.zeros_like(out)
        out[:, :F.NLIMBS] = body
    return out[:, :F.NLIMBS]


def _mul_safe_limbs(rng, n, kind):
    bound = np.full(F.NLIMBS, F.MUL_SAFE)
    bound[0] = F.MUL_SAFE_0
    if kind == "extreme":  # every limb at +-its bound, signs mixed per row
        return (bound * rng.choice([-1, 1], (n, F.NLIMBS))).astype(np.int32)
    x = rng.integers(-bound, bound + 1, (n, F.NLIMBS))
    if kind == "negative":
        x = -np.abs(x)
    return x.astype(np.int32)


@pytest.mark.parametrize("kind", ["random", "extreme", "negative"])
def test_mul_limbs_equal_int64_model(kind):
    # limb for limb, not only mod p: the fused multiply computes the same
    # integers as the plain schoolbook product and reduction
    rng = np.random.default_rng({"random": 7, "extreme": 8, "negative": 9}[kind])
    a = _mul_safe_limbs(rng, 1024, kind)
    b = _mul_safe_limbs(rng, 1024, kind)
    if kind == "extreme":  # all-max and all-min rows against each other
        bound = np.abs(a[0])
        a[:2], b[:2] = [bound, -bound], [bound, bound]
    got = np.asarray(F.mul(jnp.asarray(a), jnp.asarray(b)))
    want = _mul_model(a.astype(np.int64), b.astype(np.int64))
    assert np.array_equal(got, want)
    assert np.abs(got[:, 1:]).max() <= F.MUL_SAFE
    assert np.abs(got[:, 0]).max() <= F.MUL_SAFE_0


@pytest.mark.parametrize("passes", [1, 2, 3])
def test_weak_carry_limbs_equal_int64_model(passes):
    rng = np.random.default_rng(10 + passes)
    x = rng.integers(-2**24, 2**24, (1024, F.NLIMBS))
    got = np.asarray(F.weak_carry(jnp.asarray(x, dtype=jnp.int32), passes))
    for _ in range(passes):
        carry = x >> F.RADIX
        x = x - (carry << F.RADIX)
        x[:, 1:] += carry[:, :-1]
        x[:, 0] += F.FOLD * carry[:, -1]
    assert np.array_equal(got, x)
