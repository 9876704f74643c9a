"""Domain test fixtures: TestAccount + tx builders
(ref model: src/test/TestAccount.h, TxTests.cpp op builders)."""
from __future__ import annotations

import contextlib
from typing import List, Optional

from stellar_core_tpu.crypto import SecretKey, sha256
from stellar_core_tpu.ledger import LedgerTxn, LedgerTxnRoot, open_database
from stellar_core_tpu.transactions import utils as U
from stellar_core_tpu.transactions.signature_checker import signature_hint
from stellar_core_tpu.xdr import types as T

NETWORK_PASSPHRASE = b"Test SDF Network ; September 2015"
NETWORK_ID = sha256(NETWORK_PASSPHRASE)

BASE_FEE = 100
BASE_RESERVE = 5000000
GENESIS_BALANCE = 10**17  # ~10B XLM in stroops


@contextlib.contextmanager
def native_unbuilt():
    """A host on which the native code did not build: the apply kernel
    (``native_apply.kernel_module``) and the batched XDR packer
    (``runtime.encode_many``) both report absent, so closes run the
    Python reference apply, fee loop and tail encode.  Enter it before
    the app under test is built (its parallel-apply manager probes the
    kernel once, at construction) and leave it after the app stops."""
    from unittest import mock

    from stellar_core_tpu.apply import native_apply
    from stellar_core_tpu.xdr import runtime

    with mock.patch.object(native_apply, "kernel_module", lambda: None), \
            mock.patch.object(runtime, "encode_many", lambda pairs: None):
        yield


def genesis_header(ledger_seq=1, close_time=1000, protocol_version=19):
    sv = T.StellarValue.make(
        txSetHash=b"\x00" * 32, closeTime=close_time, upgrades=[],
        ext=T.StellarValue.fields[3][1].make(
            T.StellarValueType.STELLAR_VALUE_BASIC))
    return T.LedgerHeader.make(
        ledgerVersion=protocol_version,
        previousLedgerHash=b"\x00" * 32,
        scpValue=sv,
        txSetResultHash=b"\x00" * 32,
        bucketListHash=b"\x00" * 32,
        ledgerSeq=ledger_seq,
        totalCoins=10**18,
        feePool=0,
        inflationSeq=0,
        idPool=0,
        baseFee=BASE_FEE,
        baseReserve=BASE_RESERVE,
        maxTxSetSize=100,
        skipList=[b"\x00" * 32] * 4,
        ext=T.LedgerHeader.fields[14][1].make(0),
    )


class TestLedger:
    """In-memory root + genesis account.  ``protocol_version`` pins the
    genesis header's ledgerVersion so the hard-coded v19 version gates
    can be exercised at every gated protocol (ROADMAP item 3 /
    tests/test_protocol_versions.py)."""

    def __init__(self, protocol_version: int = 19):
        self.protocol_version = protocol_version
        self.db = open_database(":memory:")
        self.root_txn = LedgerTxnRoot(self.db)
        self.root_key = SecretKey(sha256(b"genesis-root"))
        hdr = genesis_header(protocol_version=protocol_version)
        with LedgerTxn(self.root_txn) as ltx:
            ltx.set_header(hdr)
            # bootstrap: write header first so put() can stamp seq
            ltx.commit()
        with LedgerTxn(self.root_txn) as ltx:
            ltx.put(U.make_account_entry(
                self.root().account_id, GENESIS_BALANCE, seq_num=0))
            ltx.commit()

    def root(self) -> "TestAccount":
        return TestAccount(self, self.root_key)

    def header(self):
        return self.root_txn.header()


class TestAccount:
    """Keypair + auto seq-num bookkeeping (ref TestAccount)."""

    def __init__(self, ledger: TestLedger, secret: SecretKey):
        self.ledger = ledger
        self.secret = secret
        self.account_id = secret.public_key().raw

    @classmethod
    def from_name(cls, ledger: TestLedger, name: str) -> "TestAccount":
        return cls(ledger, SecretKey(sha256(name.encode())))

    def network_id(self) -> bytes:
        """Override when the account signs for a non-default network."""
        return NETWORK_ID

    def loaded_seq(self) -> int:
        with LedgerTxn(self.ledger.root_txn) as ltx:
            e = ltx.load_account(self.account_id)
            ltx.rollback()
        return e.data.value.seqNum if e is not None else 0

    def next_seq(self) -> int:
        return self.loaded_seq() + 1

    # -- op builders (ref TxTests.cpp) -------------------------------------

    def op(self, body_type, body_value=None, source=None):
        return T.Operation.make(
            sourceAccount=(None if source is None
                           else T.muxed_account(source)),
            body=T.OperationBody.make(body_type, body_value))

    def op_create_account(self, dest: bytes, balance: int):
        return self.op(T.OperationType.CREATE_ACCOUNT,
                       T.CreateAccountOp.make(
                           destination=T.account_id(dest),
                           startingBalance=balance))

    def op_payment(self, dest: bytes, amount: int, asset=None):
        return self.op(T.OperationType.PAYMENT, T.PaymentOp.make(
            destination=T.muxed_account(dest),
            asset=asset or U.asset_native(),
            amount=amount))

    def op_change_trust(self, asset, limit=U.INT64_MAX):
        return self.op(T.OperationType.CHANGE_TRUST, T.ChangeTrustOp.make(
            line=T.ChangeTrustAsset.make(asset.type, asset.value),
            limit=limit))

    def op_bump_seq(self, to: int):
        return self.op(T.OperationType.BUMP_SEQUENCE,
                       T.BumpSequenceOp.make(bumpTo=to))

    def op_manage_data(self, name: bytes, value: Optional[bytes]):
        return self.op(T.OperationType.MANAGE_DATA, T.ManageDataOp.make(
            dataName=name, dataValue=value))

    def op_set_options(self, **kw):
        return self.op(T.OperationType.SET_OPTIONS, T.SetOptionsOp.make(
            inflationDest=kw.get("inflation_dest"),
            clearFlags=kw.get("clear_flags"),
            setFlags=kw.get("set_flags"),
            masterWeight=kw.get("master_weight"),
            lowThreshold=kw.get("low"),
            medThreshold=kw.get("med"),
            highThreshold=kw.get("high"),
            homeDomain=kw.get("home_domain"),
            signer=kw.get("signer")))

    def op_merge(self, dest: bytes):
        return self.op(T.OperationType.ACCOUNT_MERGE,
                       T.muxed_account(dest))

    def op_create_claimable_balance(self, asset, amount, claimants):
        """claimants: list of (dest_account_id, ClaimPredicate|None)."""
        cls = []
        for dest, pred in claimants:
            if pred is None:
                pred = T.ClaimPredicate.make(
                    T.ClaimPredicateType.CLAIM_PREDICATE_UNCONDITIONAL)
            cls.append(T.Claimant.make(
                T.ClaimantType.CLAIMANT_TYPE_V0,
                T.Claimant.arms[T.ClaimantType.CLAIMANT_TYPE_V0][1].make(
                    destination=T.account_id(dest), predicate=pred)))
        return self.op(T.OperationType.CREATE_CLAIMABLE_BALANCE,
                       T.CreateClaimableBalanceOp.make(
                           asset=asset, amount=amount, claimants=cls))

    def op_claim_claimable_balance(self, balance_id):
        return self.op(T.OperationType.CLAIM_CLAIMABLE_BALANCE,
                       T.ClaimClaimableBalanceOp.make(balanceID=balance_id))

    def op_clawback_claimable_balance(self, balance_id):
        return self.op(T.OperationType.CLAWBACK_CLAIMABLE_BALANCE,
                       T.ClawbackClaimableBalanceOp.make(
                           balanceID=balance_id))

    def op_begin_sponsoring(self, sponsored_id: bytes, source=None):
        return self.op(T.OperationType.BEGIN_SPONSORING_FUTURE_RESERVES,
                       T.BeginSponsoringFutureReservesOp.make(
                           sponsoredID=T.account_id(sponsored_id)),
                       source=source)

    def op_end_sponsoring(self, source=None):
        return self.op(T.OperationType.END_SPONSORING_FUTURE_RESERVES,
                       None, source=source)

    def op_revoke_sponsorship_key(self, ledger_key, source=None):
        return self.op(
            T.OperationType.REVOKE_SPONSORSHIP,
            T.RevokeSponsorshipOp.make(
                T.RevokeSponsorshipType.REVOKE_SPONSORSHIP_LEDGER_ENTRY,
                ledger_key), source=source)

    def op_revoke_sponsorship_signer(self, account_id, signer_key,
                                     source=None):
        arm = T.RevokeSponsorshipOp.arms[
            T.RevokeSponsorshipType.REVOKE_SPONSORSHIP_SIGNER][1]
        return self.op(
            T.OperationType.REVOKE_SPONSORSHIP,
            T.RevokeSponsorshipOp.make(
                T.RevokeSponsorshipType.REVOKE_SPONSORSHIP_SIGNER,
                arm.make(accountID=T.account_id(account_id),
                         signerKey=signer_key)), source=source)

    def op_change_trust_pool(self, asset_a, asset_b, limit=U.INT64_MAX):
        params = T.LiquidityPoolParameters.make(
            T.LiquidityPoolType.LIQUIDITY_POOL_CONSTANT_PRODUCT,
            T.LiquidityPoolConstantProductParameters.make(
                assetA=asset_a, assetB=asset_b,
                fee=T.LIQUIDITY_POOL_FEE_V18))
        return self.op(T.OperationType.CHANGE_TRUST, T.ChangeTrustOp.make(
            line=T.ChangeTrustAsset.make(
                T.AssetType.ASSET_TYPE_POOL_SHARE, params),
            limit=limit))

    def op_pool_deposit(self, pool_id, max_a, max_b,
                        min_price=(1, 10**7), max_price=(10**7, 1)):
        return self.op(T.OperationType.LIQUIDITY_POOL_DEPOSIT,
                       T.LiquidityPoolDepositOp.make(
                           liquidityPoolID=pool_id,
                           maxAmountA=max_a, maxAmountB=max_b,
                           minPrice=T.Price.make(n=min_price[0],
                                                 d=min_price[1]),
                           maxPrice=T.Price.make(n=max_price[0],
                                                 d=max_price[1])))

    def op_pool_withdraw(self, pool_id, amount, min_a=0, min_b=0):
        return self.op(T.OperationType.LIQUIDITY_POOL_WITHDRAW,
                       T.LiquidityPoolWithdrawOp.make(
                           liquidityPoolID=pool_id, amount=amount,
                           minAmountA=min_a, minAmountB=min_b))

    def fee_bump(self, inner_env, fee: Optional[int] = None,
                 fee_source: Optional["TestAccount"] = None):
        """Wrap a v1 envelope in a fee-bump signed by fee_source (default:
        self)."""
        src = fee_source or self
        inner_ops = len(inner_env.value.tx.operations)
        fb = T.FeeBumpTransaction.make(
            feeSource=T.muxed_account(src.account_id),
            fee=fee if fee is not None else BASE_FEE * (inner_ops + 1) * 2,
            innerTx=T.FeeBumpTransaction.fields[2][1].make(
                T.EnvelopeType.ENVELOPE_TYPE_TX, inner_env.value),
            ext=T.FeeBumpTransaction.fields[3][1].make(0))
        payload = T.TransactionSignaturePayload.make(
            networkId=src.network_id(),
            taggedTransaction=T.TransactionSignaturePayload.fields[1][1]
            .make(T.EnvelopeType.ENVELOPE_TYPE_TX_FEE_BUMP, fb))
        h = sha256(T.TransactionSignaturePayload.encode(payload))
        sig = T.DecoratedSignature.make(
            hint=signature_hint(src.secret.public_key().raw),
            signature=src.secret.sign(h))
        return T.TransactionEnvelope.make(
            T.EnvelopeType.ENVELOPE_TYPE_TX_FEE_BUMP,
            T.FeeBumpTransactionEnvelope.make(tx=fb, signatures=[sig]))

    # -- tx builder ---------------------------------------------------------

    def tx(self, ops: List, fee: Optional[int] = None,
           seq: Optional[int] = None, cond=None,
           extra_signers: List[SecretKey] = ()):
        tx = T.Transaction.make(
            sourceAccount=T.muxed_account(self.account_id),
            fee=fee if fee is not None else BASE_FEE * len(ops),
            seqNum=seq if seq is not None else self.next_seq(),
            cond=cond or T.Preconditions.make(
                T.PreconditionType.PRECOND_NONE),
            memo=T.MEMO_NONE_VALUE,
            operations=ops,
            ext=T.Transaction.fields[6][1].make(0),
        )
        payload = T.TransactionSignaturePayload.make(
            networkId=self.network_id(),
            taggedTransaction=T.TransactionSignaturePayload.fields[1][1]
            .make(T.EnvelopeType.ENVELOPE_TYPE_TX, tx))
        h = sha256(T.TransactionSignaturePayload.encode(payload))
        sigs = []
        for sk in [self.secret, *extra_signers]:
            sigs.append(T.DecoratedSignature.make(
                hint=signature_hint(sk.public_key().raw),
                signature=sk.sign(h)))
        return T.TransactionEnvelope.make(
            T.EnvelopeType.ENVELOPE_TYPE_TX,
            T.TransactionV1Envelope.make(tx=tx, signatures=sigs))

    # -- execution helpers ---------------------------------------------------

    def apply(self, env, expect_success=True):
        """processFeeSeqNum + apply against the root, like one-tx ledger
        close; returns (ok, result).  Handles fee-bump envelopes too.
        Each apply advances the ledger seq first, like a real close —
        starting seqnums (ledgerSeq << 32) and merge SEQNUM_TOO_FAR
        semantics depend on it."""
        from stellar_core_tpu.transactions.frame import \
            tx_frame_from_envelope

        frame = tx_frame_from_envelope(NETWORK_ID, env)
        with LedgerTxn(self.ledger.root_txn) as ltx:
            hdr = ltx.header()
            ltx.set_header(hdr._replace(ledgerSeq=hdr.ledgerSeq + 1))
            frame.process_fee_seq_num(ltx, base_fee=BASE_FEE)
            ok, result, meta = frame.apply(ltx)
            ltx.commit()
        if expect_success:
            assert ok, result
        return ok, result

    def entry(self, key):
        with LedgerTxn(self.ledger.root_txn) as ltx:
            e = ltx.load(key)
            ltx.rollback()
        return e

    def account_entry(self):
        with LedgerTxn(self.ledger.root_txn) as ltx:
            e = ltx.load_account(self.account_id)
            ltx.rollback()
        return e

    def check_valid(self, env):
        from stellar_core_tpu.transactions import TransactionFrame

        frame = TransactionFrame(NETWORK_ID, env)
        with LedgerTxn(self.ledger.root_txn) as ltx:
            res = frame.check_valid(ltx)
            ltx.rollback()
        return res

    def balance(self) -> int:
        with LedgerTxn(self.ledger.root_txn) as ltx:
            e = ltx.load_account(self.account_id)
            ltx.rollback()
        return e.data.value.balance if e is not None else -1

    def exists(self) -> bool:
        with LedgerTxn(self.ledger.root_txn) as ltx:
            e = ltx.load_account(self.account_id)
            ltx.rollback()
        return e is not None

    def create(self, name: str, balance: int) -> "TestAccount":
        """Create a funded child account."""
        child = TestAccount.from_name(self.ledger, name)
        env = self.tx([self.op_create_account(child.account_id, balance)])
        self.apply(env)
        return child
