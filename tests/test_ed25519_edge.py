"""libsodium edge-case vectors: small-order / non-canonical / malleable
inputs must get the same verdict from the executable spec, the CPU backend
(OpenSSL + blacklist prefilter), the XLA kernel, and the Pallas kernel
(interpret mode) — pinning the whole framework to libsodium
crypto_sign_verify_detached semantics (ref src/crypto/SecretKey.cpp:428-459;
VERDICT r2 weak #4)."""
import numpy as np
import pytest

from stellar_core_tpu.crypto import SecretKey, sha256
from stellar_core_tpu.crypto import ed25519 as ed
from stellar_core_tpu.crypto import ed25519_ref as ref

# (pubkey, sig, msg, label) edge inputs.  Expected verdicts come from the
# spec; the point of the tests is four-way agreement.
VECTORS = ref.edge_vectors()


def test_edge_vectors_sign_like_the_cpu_backend():
    """The spec signer behind the vectors' valid row is the CPU
    backend's signer, byte for byte."""
    sk = SecretKey(sha256(b"edge0"))
    pk, sig, msg, label = VECTORS[0]
    assert label == "valid"
    assert pk == sk.public_key().raw and sig == sk.sign(msg)


def test_spec_verdicts():
    """Sanity: the spec rejects every malformed vector and accepts the
    valid one."""
    for pk, sig, msg, label in VECTORS:
        got = ref.verify(pk, sig, msg)
        assert got == (label == "valid"), label


def test_cpu_backend_matches_spec():
    for pk, sig, msg, label in VECTORS:
        assert ed.raw_verify(pk, sig, msg) == ref.verify(pk, sig, msg), label


def test_xla_kernel_matches_spec():
    from stellar_core_tpu.ops.ed25519_kernel import verify_batch

    n = len(VECTORS)
    pk = np.frombuffer(b"".join(v[0] for v in VECTORS),
                       np.uint8).reshape(n, 32)
    sg = np.frombuffer(b"".join(v[1] for v in VECTORS),
                       np.uint8).reshape(n, 64)
    mg = np.frombuffer(b"".join(v[2] for v in VECTORS),
                       np.uint8).reshape(n, 32)
    got = np.asarray(verify_batch(pk, sg, mg))
    for (pkb, sig, msg, label), g in zip(VECTORS, got):
        assert bool(g) == ref.verify(pkb, sig, msg), label


@pytest.mark.slow
def test_pallas_kernel_matches_spec_interpret():
    from stellar_core_tpu.ops.ed25519_pallas import verify_batch

    n = len(VECTORS)
    pk = np.frombuffer(b"".join(v[0] for v in VECTORS),
                       np.uint8).reshape(n, 32)
    sg = np.frombuffer(b"".join(v[1] for v in VECTORS),
                       np.uint8).reshape(n, 64)
    mg = np.frombuffer(b"".join(v[2] for v in VECTORS),
                       np.uint8).reshape(n, 32)
    got = np.asarray(verify_batch(pk, sg, mg, interpret=True))
    for (pkb, sig, msg, label), g in zip(VECTORS, got):
        assert bool(g) == ref.verify(pkb, sig, msg), label


def test_torsion_subgroup_structure():
    """The generated blacklist covers the full 8-torsion subgroup."""
    pts = ref._torsion_points()
    assert len(pts) == 8
    for pt in pts:
        assert ref._is_identity(ref.scalar_mult(8, ref.to_extended(pt)))
    # contains identity and (0,-1)
    assert (0, 1) in pts and (0, ref.P - 1) in pts
    # 10 encodings: 8 canonical + 2 extra -0 sign variants
    assert len(ref.SMALL_ORDER_ENCODINGS) == 10
