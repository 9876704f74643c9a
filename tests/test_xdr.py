"""XDR runtime + schema tests (ref test model: xdrpp round-trip tests and
src/util/test/XDRStreamTests.cpp)."""
import pytest

from stellar_core_tpu.xdr import XdrError, xdr_sha256
from stellar_core_tpu.xdr import runtime as R
from stellar_core_tpu.xdr import types as T


def test_primitive_encodings():
    assert R.Int.encode(1) == b"\x00\x00\x00\x01"
    assert R.Int.encode(-1) == b"\xff\xff\xff\xff"
    assert R.Uint.encode(2**32 - 1) == b"\xff\xff\xff\xff"
    assert R.Hyper.encode(-2) == b"\xff" * 7 + b"\xfe"
    assert R.Uhyper.encode(2**64 - 1) == b"\xff" * 8
    assert R.Bool.encode(True) == b"\x00\x00\x00\x01"
    with pytest.raises(XdrError):
        R.Int.encode(2**31)
    with pytest.raises(XdrError):
        R.Uint.encode(-1)


def test_opaque_padding():
    assert R.Opaque(3).encode(b"abc") == b"abc\x00"
    assert R.VarOpaque().encode(b"abcde") == (
        b"\x00\x00\x00\x05abcde\x00\x00\x00"
    )
    # nonzero padding rejected on decode
    with pytest.raises(XdrError):
        R.Opaque(3).decode(b"abcX")
    assert R.Opaque(3).decode(b"abc\x00") == b"abc"


def test_var_opaque_max_enforced():
    with pytest.raises(XdrError):
        R.VarOpaque(4).encode(b"abcde")
    data = b"\x00\x00\x00\x05abcde\x00\x00\x00"
    with pytest.raises(XdrError):
        R.VarOpaque(4).decode(data)


def test_optional():
    t = R.Option(R.Int)
    assert t.encode(None) == b"\x00\x00\x00\x00"
    assert t.encode(7) == b"\x00\x00\x00\x01\x00\x00\x00\x07"
    assert t.decode(t.encode(None)) is None
    assert t.decode(t.encode(7)) == 7


def test_struct_union_roundtrip():
    v = T.Price.make(n=3, d=7)
    assert T.Price.decode(T.Price.encode(v)) == v
    m = T.Memo.make(T.MemoType.MEMO_ID, 42)
    assert T.Memo.decode(T.Memo.encode(m)) == m
    with pytest.raises(XdrError):
        T.Memo.make(99, None)  # unknown discriminant


def test_enum_rejects_unknown_value_on_decode():
    bad = b"\x00\x00\x00\x63"  # 99
    with pytest.raises(XdrError):
        T.MemoType.decode(bad)


def _example_account_entry():
    key = b"\x07" * 32
    return T.AccountEntry.make(
        accountID=T.account_id(key),
        balance=10**9,
        seqNum=2**33,
        numSubEntries=2,
        inflationDest=None,
        flags=T.AUTH_REQUIRED_FLAG,
        homeDomain=b"example.com",
        thresholds=b"\x01\x00\x01\x02",
        signers=[T.Signer.make(
            key=T.SignerKey.make(
                T.SignerKeyType.SIGNER_KEY_TYPE_ED25519, b"\x09" * 32),
            weight=5)],
        ext=T.AccountEntry.fields[9][1].make(0),
    )


def test_ledger_entry_roundtrip():
    acc = _example_account_entry()
    le = T.LedgerEntry.make(
        lastModifiedLedgerSeq=17,
        data=T.LedgerEntryData.make(T.LedgerEntryType.ACCOUNT, acc),
        ext=T.LedgerEntry.fields[2][1].make(0),
    )
    b = T.LedgerEntry.encode(le)
    assert T.LedgerEntry.decode(b) == le
    # canonical: re-encode of decode is byte-identical
    assert T.LedgerEntry.encode(T.LedgerEntry.decode(b)) == b


def test_transaction_envelope_roundtrip():
    key = b"\x03" * 32
    acc = T.muxed_account(key)
    pay = T.PaymentOp.make(
        destination=acc,
        asset=T.Asset.make(T.AssetType.ASSET_TYPE_NATIVE),
        amount=5_0000000,
    )
    op = T.Operation.make(
        sourceAccount=None,
        body=T.OperationBody.make(T.OperationType.PAYMENT, pay),
    )
    tx = T.Transaction.make(
        sourceAccount=acc,
        fee=100,
        seqNum=7,
        cond=T.Preconditions.make(T.PreconditionType.PRECOND_NONE),
        memo=T.MEMO_NONE_VALUE,
        operations=[op],
        ext=T.Transaction.fields[6][1].make(0),
    )
    env = T.TransactionEnvelope.make(
        T.EnvelopeType.ENVELOPE_TYPE_TX,
        T.TransactionV1Envelope.make(
            tx=tx,
            signatures=[T.DecoratedSignature.make(
                hint=b"\x03\x03\x03\x03", signature=b"\x05" * 64)],
        ),
    )
    b = T.TransactionEnvelope.encode(env)
    assert T.TransactionEnvelope.decode(b) == env
    assert len(xdr_sha256(T.TransactionEnvelope, env)) == 32


def test_scp_statement_roundtrip():
    st = T.SCPStatement.make(
        nodeID=T.account_id(b"\x01" * 32),
        slotIndex=9,
        pledges=T.SCPStatementPledges.make(
            T.SCPStatementType.SCP_ST_NOMINATE,
            T.SCPNomination.make(
                quorumSetHash=b"\x02" * 32,
                votes=[b"v1", b"v2"],
                accepted=[],
            ),
        ),
    )
    env = T.SCPEnvelope.make(statement=st, signature=b"\x04" * 64)
    b = T.SCPEnvelope.encode(env)
    assert T.SCPEnvelope.decode(b) == env


def test_recursive_quorum_set():
    def nid(i):
        return T.account_id(bytes([i]) * 32)

    qs = T.SCPQuorumSet.make(
        threshold=2,
        validators=[nid(1)],
        innerSets=[T.SCPQuorumSet.make(
            threshold=1, validators=[nid(2), nid(3)], innerSets=[])],
    )
    b = T.SCPQuorumSet.encode(qs)
    assert T.SCPQuorumSet.decode(b) == qs


def test_ledger_header_roundtrip():
    sv = T.StellarValue.make(
        txSetHash=b"\x0a" * 32,
        closeTime=123456,
        upgrades=[],
        ext=T.StellarValue.fields[3][1].make(
            T.StellarValueType.STELLAR_VALUE_BASIC),
    )
    hdr = T.LedgerHeader.make(
        ledgerVersion=19,
        previousLedgerHash=b"\x0b" * 32,
        scpValue=sv,
        txSetResultHash=b"\x0c" * 32,
        bucketListHash=b"\x0d" * 32,
        ledgerSeq=100,
        totalCoins=10**15,
        feePool=500,
        inflationSeq=0,
        idPool=99,
        baseFee=100,
        baseReserve=5000000,
        maxTxSetSize=1000,
        skipList=[b"\x00" * 32] * 4,
        ext=T.LedgerHeader.fields[14][1].make(0),
    )
    b = T.LedgerHeader.encode(hdr)
    assert T.LedgerHeader.decode(b) == hdr


def test_trailing_bytes_rejected():
    b = T.Price.encode(T.Price.make(n=1, d=2))
    with pytest.raises(XdrError):
        T.Price.decode(b + b"\x00\x00\x00\x00")


def test_transaction_result_roundtrip():
    res = T.TransactionResult.make(
        feeCharged=100,
        result=T.TransactionResult.fields[1][1].make(
            T.TransactionResultCode.txSUCCESS,
            [T.OperationResult.make(
                T.OperationResultCode.opINNER,
                T.OperationResultTr.make(
                    T.OperationType.PAYMENT,
                    T.PaymentResult.make(
                        T.PaymentResultCode.PAYMENT_SUCCESS)))],
        ),
        ext=T.TransactionResult.fields[2][1].make(0),
    )
    b = T.TransactionResult.encode(res)
    assert T.TransactionResult.decode(b) == res


def test_adversarial_nesting_depth_bounded():
    # a ~400-level-deep SCPQuorumSet must fail with XdrError, not
    # RecursionError (wire-facing decode contract)
    inner = T.SCPQuorumSet.make(threshold=1, validators=[], innerSets=[])
    for _ in range(400):
        inner = T.SCPQuorumSet.make(
            threshold=1, validators=[], innerSets=[inner])
    import sys
    old = sys.getrecursionlimit()
    sys.setrecursionlimit(10000)
    try:
        data = T.SCPQuorumSet.encode(inner)
    finally:
        sys.setrecursionlimit(old)
    with pytest.raises(XdrError):
        T.SCPQuorumSet.decode(data)


def test_native_encoder_differential():
    """The C schema-VM packer (native/xdr_pack.c) must be byte-identical
    to the Python combinator walk on every type it compiled — checked on
    decoded wire samples AND error behavior."""
    import pytest

    from stellar_core_tpu.xdr import types as T
    from stellar_core_tpu.xdr.runtime import XdrError

    # builds native/_xdrpack.so from source when this worker has not yet
    assert T.ensure_native_encode(), "g++ build of the native encoder failed"

    def py_encode(t, v):
        out = []
        t.pack(v, out)
        return b"".join(out)

    from stellar_core_tpu.crypto import SecretKey, sha256
    from stellar_core_tpu.transactions import utils as U

    sk = SecretKey(sha256(b"native-diff"))
    pub = sk.public_key().raw
    samples = [
        (T.LedgerEntry, U.make_account_entry(pub, 12345, seq_num=7)),
        (T.LedgerEntry, U.make_trustline_entry(
            pub, U.make_asset(b"USD", pub), balance=55)),
        (T.Price, T.Price.make(n=3, d=7)),
        (T.Asset, U.asset_native()),
        (T.SCPQuorumSet, T.SCPQuorumSet.make(
            threshold=2, validators=[T.account_id(pub)], innerSets=[
                T.SCPQuorumSet.make(threshold=1,
                                    validators=[T.account_id(pub)],
                                    innerSets=[])])),
        (T.ClaimPredicate, T.ClaimPredicate.make(
            T.ClaimPredicateType.CLAIM_PREDICATE_OR, [
                T.ClaimPredicate.make(
                    T.ClaimPredicateType.CLAIM_PREDICATE_UNCONDITIONAL),
                T.ClaimPredicate.make(
                    T.ClaimPredicateType
                    .CLAIM_PREDICATE_BEFORE_ABSOLUTE_TIME, 99)])),
    ]
    for t, v in samples:
        enc = t.encode(v)
        assert enc == py_encode(t, v)
        # round-trip through decode and re-encode both ways
        v2 = t.decode(enc)
        assert t.encode(v2) == py_encode(t, v2) == enc
    # error parity: bad sizes/ranges still raise XdrError
    with pytest.raises(XdrError):
        T.Price.encode(T.Price.make(n=2**31, d=1))
    with pytest.raises(XdrError):
        T.Hash.encode(b"short")
