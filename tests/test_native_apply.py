"""Native GIL-free apply kernel (ISSUE 6 tentpole): bit-identity of
kernel-applied clusters against the Python reference apply, the
decline-to-Python fallback, the packed-delta merge tier, and the
reference path of a host on which the kernel did not build
(``txtest.native_unbuilt``).

The consensus property: for ANY tx set, closes with the native kernel
engaged must produce byte-identical ledger header hash, bucket-list
hash and tx meta versus the Python reference apply — across worker
counts (0 sequential / 2 / 4), and across PYTHONHASHSEED values
(subprocess).
A kernel-ineligible tx inside an otherwise-eligible set must route its
cluster (and only its cluster) through the Python path and STILL
match.
"""
import hashlib
import os
import subprocess
import sys
import threading

import pytest

from stellar_core_tpu.main import Application, test_config
from stellar_core_tpu.simulation.load_generator import LoadGenerator
from stellar_core_tpu.utils.clock import ClockMode, VirtualClock
from stellar_core_tpu.xdr import types as T

from .test_parallel_apply import (
    _assert_identical, _close_and_fingerprint, _run_workload,
)
from .txtest import native_unbuilt


def test_kernel_builds():
    from stellar_core_tpu.native import get_apply_kernel

    assert get_apply_kernel() is not None, \
        "apply kernel failed to build (g++ is baked into the image)"


# -- the bit-identity property (native vs Python reference) ------------------

def test_native_matches_python_across_worker_counts():
    """Randomized pay/mixed/crossing workload: kernel on vs a host
    without the kernel at workers 2 and 4 — identical fingerprints, and
    the kernel must actually engage (hits > 0, declines accounted)."""
    with native_unbuilt():
        python_fps, python_stats = _run_workload(2)
    assert python_stats["native_hits"] == 0
    for workers in (2, 4):
        fps, stats = _run_workload(workers)
        _assert_identical(python_fps, fps, f"native workers={workers}")
        assert stats["native_hits"] > 0, \
            f"kernel never engaged at workers={workers}: {stats}"
        assert stats["aborts"] == 0, stats


def test_native_inline_workers0_matches_sequential():
    """A one-cluster plan runs on the close thread under the worker
    pool: the path-payment closes are one book-pair cluster, applied
    through the kernel with no pool round trip — same bytes as the
    sequential close (workers 0)."""
    seq, _, _ = _pathpay_workload(0, n_closes=2)
    tasks = []

    def spy(app):
        mgr = app.parallel_apply
        run_task = mgr._run_task

        def recording(clusters, *args):
            tasks.append((len(clusters), threading.current_thread()))
            return run_task(clusters, *args)
        mgr._run_task = recording

    fps, stats, _ = _pathpay_workload(2, n_closes=2, app_hook=spy)
    _assert_identical(seq, fps, "one-cluster plan on the close thread")
    assert stats["native_hits"] > 0, stats
    single = [t for n, t in tasks if n == 1]
    assert single, tasks
    assert all(t is threading.main_thread() for t in single), tasks


def test_kill_switch_restores_pure_python_path():
    seq, _ = _run_workload(0, n_closes=2)
    with native_unbuilt():
        fps, stats = _run_workload(2, n_closes=2)
    _assert_identical(seq, fps, "kernel not built")
    assert stats["native_hits"] == 0
    assert stats["native_declines"] == 0


# -- decline paths -----------------------------------------------------------

def _mk_app(workers, **kw):
    app = Application(VirtualClock(ClockMode.VIRTUAL_TIME), test_config(
        TESTING_UPGRADE_MAX_TX_SET_SIZE=300,
        PARALLEL_APPLY_WORKERS=workers, **kw))
    app.start()
    return app


def _bounded_payment(lg, src, dest, amount):
    """A payment with time-bound preconditions: applies fine but is NOT
    kernel-shaped (PRECOND_TIME stays host-side)."""
    from stellar_core_tpu.crypto import sha256
    from stellar_core_tpu.transactions import utils as U
    from stellar_core_tpu.transactions.signature_checker import \
        signature_hint

    op = T.Operation.make(
        sourceAccount=None,
        body=T.OperationBody.make(
            T.OperationType.PAYMENT,
            T.PaymentOp.make(destination=T.muxed_account(dest),
                             asset=U.asset_native(), amount=amount)))
    tx = T.Transaction.make(
        sourceAccount=T.muxed_account(src.public_key().raw),
        fee=100,
        seqNum=lg._next_seq(src),
        cond=T.Preconditions.make(
            T.PreconditionType.PRECOND_TIME,
            T.TimeBounds.make(minTime=0, maxTime=0)),
        memo=T.MEMO_NONE_VALUE,
        operations=[op],
        ext=T.Transaction.fields[6][1].make(0))
    payload = T.TransactionSignaturePayload.make(
        networkId=lg.network_id,
        taggedTransaction=T.TransactionSignaturePayload.fields[1][1]
        .make(T.EnvelopeType.ENVELOPE_TYPE_TX, tx))
    h = sha256(T.TransactionSignaturePayload.encode(payload))
    sig = T.DecoratedSignature.make(
        hint=signature_hint(src.public_key().raw),
        signature=src.sign(h))
    return T.TransactionEnvelope.make(
        T.EnvelopeType.ENVELOPE_TYPE_TX,
        T.TransactionV1Envelope.make(tx=tx, signatures=[sig]))


def _ineligible_mid_cluster_workload(workers, **kw):
    """Pairs payments with ONE structurally-ineligible tx injected: its
    cluster must fall to the Python path while the rest stay native."""
    app = _mk_app(workers, **kw)
    lg = LoadGenerator(app)
    lg.payment_pattern = "pairs"
    lg.create_accounts(40)
    fps = []
    for _ in range(3):
        envs = lg.generate_payments(60)
        # the injected tx shares account 0's pair-cluster mid-set
        envs.append(_bounded_payment(
            lg, lg.accounts[0], lg.accounts[1].public_key().raw, 7))
        admitted = sum(1 for env in envs
                       if app.herder.recv_transaction(env) == 0)
        assert admitted == len(envs)
        _close_and_fingerprint(app, fps)
    stats = dict(app.parallel_apply.stats)
    app.graceful_stop()
    return fps, stats


def test_ineligible_tx_mid_cluster_falls_back_and_matches():
    seq, _ = _ineligible_mid_cluster_workload(0)
    fps, stats = _ineligible_mid_cluster_workload(2)
    _assert_identical(seq, fps, "ineligible mid-cluster")
    assert stats["native_hits"] > 0, stats
    # the bounded tx's cluster was never offered to the kernel
    assert stats["native_off"] > 0, stats
    assert stats["aborts"] == 0, stats


def _extra_signer_workload(workers, app_hook=None):
    """State-level decline: an account grows a second signer, so later
    payments from it are kernel-SHAPED but the kernel's account parse
    refuses (signers stay host-side) — decline, Python fallback, same
    bytes."""
    from stellar_core_tpu.crypto import sha256

    app = _mk_app(workers)
    if app_hook is not None:
        app_hook(app)
    lg = LoadGenerator(app)
    lg.payment_pattern = "pairs"
    lg.create_accounts(20)
    signer_key = sha256(b"native-apply-extra-signer")
    src = lg.accounts[0]
    op = T.Operation.make(
        sourceAccount=None,
        body=T.OperationBody.make(
            T.OperationType.SET_OPTIONS,
            T.SetOptionsOp.make(
                inflationDest=None, clearFlags=None, setFlags=None,
                masterWeight=None, lowThreshold=None, medThreshold=None,
                highThreshold=None, homeDomain=None,
                signer=T.Signer.make(
                    key=T.SignerKey.make(
                        T.SignerKeyType.SIGNER_KEY_TYPE_ED25519,
                        signer_key),
                    weight=1))))
    env = lg._sign_tx(src, [op], 100)
    assert app.herder.recv_transaction(env) == 0
    fps = []
    _close_and_fingerprint(app, fps)
    for _ in range(2):
        envs = lg.generate_payments(40)
        admitted = sum(1 for e in envs
                       if app.herder.recv_transaction(e) == 0)
        assert admitted == len(envs)
        _close_and_fingerprint(app, fps)
    stats = dict(app.parallel_apply.stats)
    app.graceful_stop()
    return fps, stats


def test_unsupported_account_state_declines_and_matches():
    seq, _ = _extra_signer_workload(0)
    fps, stats = _extra_signer_workload(2)
    _assert_identical(seq, fps, "extra-signer decline")
    assert stats["native_declines"] > 0, stats
    assert any("unsupported account shape" in r
               for r in stats["native_decline_reasons"]), \
        stats["native_decline_reasons"]
    assert stats["native_hits"] > 0, stats


def test_single_cluster_ring_goes_native_inline():
    """The adversarial ring (one conflict component) used to force a
    planner refusal; with the kernel it becomes a single-cluster native
    plan applied inline on the close thread."""
    seq, _ = _run_workload(0, pattern="ring", n_closes=2)
    fps, stats = _run_workload(2, pattern="ring", n_closes=2)
    _assert_identical(seq, fps, "ring native")
    assert stats["native_hits"] > 0, stats


# -- ISSUE 13: kernel-complete apply (credit / trust / path / modify) --------

def _hit_rate(stats) -> float:
    clusters = stats["native_hits"] + stats["native_declines"] + \
        stats["native_off"]
    return stats["native_hits"] / clusters if clusters else 0.0


def _credit_workload(workers, n_closes=3, txs=60):
    """Credit-asset payments over disjoint pairs + changeTrust
    create/update salt — the credit-heavy shape real traffic has."""
    app = _mk_app(workers)
    lg = LoadGenerator(app)
    lg.create_accounts(40)
    lg.setup_credit()
    fps = []
    for _ in range(n_closes):
        envs = lg.generate_credit_mix(txs, trust_pct=15)
        assert sum(1 for e in envs
                   if app.herder.recv_transaction(e) == 0) == len(envs)
        _close_and_fingerprint(app, fps)
    stats = dict(app.parallel_apply.stats)
    hits = {name: m.count for name, m in app.metrics._metrics.items()
            if name.startswith("apply.native.hit.")}
    app.graceful_stop()
    return fps, stats, hits


def test_credit_mix_goes_native_and_matches():
    with native_unbuilt():
        seq, _, _ = _credit_workload(0)
    fps, stats, hits = _credit_workload(2)
    _assert_identical(seq, fps, "credit mix")
    assert stats["aborts"] == 0, stats
    # declines on the credit mix are now bugs, not expected coverage
    # gaps (the ISSUE-13 acceptance gate)
    assert _hit_rate(stats) >= 0.9, stats
    assert hits.get("apply.native.hit.payment", 0) > 0, hits
    assert hits.get("apply.native.hit.trust", 0) > 0, hits


def test_changetrust_delete_goes_native_and_matches():
    """Trustline create (close 1) then delete via limit=0 (close 2) —
    the subentry-reserve round trip, in-kernel both ways."""
    def run(workers):
        app = _mk_app(workers)
        lg = LoadGenerator(app)
        lg.create_accounts(10)
        lg.setup_credit()
        fps = []
        for limit in (10**9, 0):
            envs = [lg.changetrust_envelope(sk, lg.credit2_asset, limit)
                    for sk in lg.accounts]
            assert sum(1 for e in envs
                       if app.herder.recv_transaction(e) == 0) == len(envs)
            _close_and_fingerprint(app, fps)
        stats = dict(app.parallel_apply.stats)
        app.graceful_stop()
        return fps, stats

    with native_unbuilt():
        seq, _ = run(0)
    fps, stats = run(2)
    _assert_identical(seq, fps, "changetrust delete")
    assert stats["native_hits"] > 0, stats
    assert stats["native_declines"] == 0, stats


def _pathpay_workload(workers, hops=2, n_closes=3, txs=40, app_hook=None):
    app = _mk_app(workers)
    if app_hook is not None:
        app_hook(app)
    lg = LoadGenerator(app)
    lg.create_accounts(24)
    maker_envs = lg.setup_path(hops=hops, makers=4)
    assert sum(1 for e in maker_envs
               if app.herder.recv_transaction(e) == 0) == len(maker_envs)
    fps = []
    _close_and_fingerprint(app, fps)
    for _ in range(n_closes):
        envs = lg.generate_path_payments(txs)
        assert sum(1 for e in envs
                   if app.herder.recv_transaction(e) == 0) == len(envs)
        _close_and_fingerprint(app, fps)
    stats = dict(app.parallel_apply.stats)
    hits = {name: m.count for name, m in app.metrics._metrics.items()
            if name.startswith("apply.native.hit.")}
    app.graceful_stop()
    return fps, stats, hits


def test_path_payments_go_native_and_match():
    """2-hop strict-send + strict-receive chains over seeded books:
    the whole close is one book-pair cluster, applied natively inline;
    bytes identical to the Python reference."""
    with native_unbuilt():
        seq, _, _ = _pathpay_workload(0)
    fps, stats, hits = _pathpay_workload(2)
    _assert_identical(seq, fps, "path payments")
    assert stats["aborts"] == 0, stats
    assert _hit_rate(stats) >= 0.9, stats
    assert hits.get("apply.native.hit.pathpay", 0) > 0, hits


def test_three_hop_path_payments_match():
    with native_unbuilt():
        seq, _, _ = _pathpay_workload(0, hops=3, n_closes=2)
    fps, stats, _ = _pathpay_workload(2, hops=3, n_closes=2)
    _assert_identical(seq, fps, "3-hop path payments")
    assert stats["native_hits"] > 0, stats


def test_live_pool_on_hop_goes_native_and_matches():
    """A LIVE liquidity pool on a hop pair quotes IN-KERNEL (r16):
    the constant-product-vs-book arbitration runs inside the crossing
    loop, bytes identical to the Python reference — at workers 0, and
    at workers 2 on a host without the kernel."""
    from stellar_core_tpu.ledger.ledger_txn import LedgerTxn
    from stellar_core_tpu.transactions import liquidity_pool as LP
    from stellar_core_tpu.transactions import utils as U

    def seed_pool(app, lg):
        # a constant-product pool on the (native, PATHA) hop pair,
        # bulk-written like the rest of the perf-rig seeding
        a_native = U.asset_native()
        a_credit = lg.path_assets[0]
        a, b = ((a_native, a_credit)
                if LP.compare_assets(a_native, a_credit) < 0
                else (a_credit, a_native))
        params = T.LiquidityPoolParameters.make(
            T.LiquidityPoolType.LIQUIDITY_POOL_CONSTANT_PRODUCT,
            T.LiquidityPoolConstantProductParameters.make(
                assetA=a, assetB=b, fee=T.LIQUIDITY_POOL_FEE_V18))
        pool_id = LP.pool_id_from_params(params)
        cp = T.LiquidityPoolEntry.fields[1][1].arms[
            T.LiquidityPoolType.LIQUIDITY_POOL_CONSTANT_PRODUCT][1].make(
            params=params.value, reserveA=10**10, reserveB=10**10,
            totalPoolShares=10**10, poolSharesTrustLineCount=1)
        lp = T.LiquidityPoolEntry.make(
            liquidityPoolID=pool_id,
            body=T.LiquidityPoolEntry.fields[1][1].make(
                T.LiquidityPoolType.LIQUIDITY_POOL_CONSTANT_PRODUCT, cp))
        with LedgerTxn(app.ledger_manager.root) as ltx:
            ltx.put(U.wrap_entry(T.LedgerEntryType.LIQUIDITY_POOL, lp))
            ltx.commit()

    def run(workers):
        app = _mk_app(workers)
        lg = LoadGenerator(app)
        lg.create_accounts(12)
        maker_envs = lg.setup_path(hops=2, makers=2)
        assert sum(1 for e in maker_envs
                   if app.herder.recv_transaction(e) == 0) == \
            len(maker_envs)
        fps = []
        _close_and_fingerprint(app, fps)
        seed_pool(app, lg)
        envs = lg.generate_path_payments(20)
        assert sum(1 for e in envs
                   if app.herder.recv_transaction(e) == 0) == len(envs)
        _close_and_fingerprint(app, fps)
        stats = dict(app.parallel_apply.stats)
        app.graceful_stop()
        return fps, stats

    with native_unbuilt():
        seq, _ = run(0)
    fps, stats = run(2)
    _assert_identical(seq, fps, "pool-on-hop native")
    assert stats["native_hits"] > 0, stats
    # the same pool hops at workers 2 on a host without the kernel:
    # the Python reference adjudicates every hop — bytes still identical
    with native_unbuilt():
        fps_off, stats_off = run(2)
    _assert_identical(seq, fps_off, "pool-on-hop Python reference")
    assert stats_off["native_hits"] == 0, stats_off
    assert stats_off["native_declines"] == 0, stats_off


def test_offer_modify_delete_go_native_and_match():
    """offerID!=0: modify re-posts at the same id (UPDATED effect),
    amount=0 deletes — the resting offer loads from the packed
    snapshot, old liabilities release, the crossing loop re-runs."""
    from stellar_core_tpu.ledger.ledger_txn import LedgerTxn

    def run(workers):
        app = _mk_app(workers)
        lg = LoadGenerator(app)
        lg.payment_pattern = "pairs"
        lg.create_accounts(12)
        lg.setup_dex()
        fps = []
        # close 1: everyone posts a resting offer (exact-ratio amounts
        # keep the 1% price-error threshold out of the picture)
        envs = [lg.offer_envelope(sk, 100, 120 + i, 100)
                for i, sk in enumerate(lg.accounts)]
        assert sum(1 for e in envs
                   if app.herder.recv_transaction(e) == 0) == len(envs)
        _close_and_fingerprint(app, fps)
        ids = {}
        with LedgerTxn(app.ledger_manager.root) as ltx:
            for sk in lg.accounts:
                offers = list(ltx.offers_by_account(sk.public_key().raw))
                assert len(offers) == 1
                ids[sk.public_key().raw] = offers[0].data.value.offerID
            ltx.rollback()
        # close 2: modify every offer (new amount + price, same id)
        envs = [lg.offer_envelope(sk, 200, 140 + i, 100,
                                  offer_id=ids[sk.public_key().raw])
                for i, sk in enumerate(lg.accounts)]
        assert sum(1 for e in envs
                   if app.herder.recv_transaction(e) == 0) == len(envs)
        _close_and_fingerprint(app, fps)
        # close 3: half delete (amount=0), half modify again
        envs = []
        for i, sk in enumerate(lg.accounts):
            oid = ids[sk.public_key().raw]
            if i % 2 == 0:
                envs.append(lg.offer_envelope(sk, 0, 1, 1, offer_id=oid))
            else:
                envs.append(lg.offer_envelope(sk, 100, 150, 100,
                                              offer_id=oid))
        assert sum(1 for e in envs
                   if app.herder.recv_transaction(e) == 0) == len(envs)
        _close_and_fingerprint(app, fps)
        stats = dict(app.parallel_apply.stats)
        app.graceful_stop()
        return fps, stats

    with native_unbuilt():
        seq, _ = run(0)
    fps, stats = run(2)
    _assert_identical(seq, fps, "offer modify/delete")
    assert stats["native_hits"] > 0, stats
    assert stats["native_declines"] == 0, stats
    assert stats["aborts"] == 0, stats


def test_decline_taxonomy_reaches_metrics():
    """A decline increments apply.native.decline.<op>.<reason> so a
    decline storm names its coverage gap in /metrics."""
    app = _mk_app(2)
    lg = LoadGenerator(app)
    lg.payment_pattern = "pairs"
    lg.create_accounts(8)
    from stellar_core_tpu.crypto import sha256

    signer_key = sha256(b"decline-taxonomy-signer")
    op = T.Operation.make(
        sourceAccount=None,
        body=T.OperationBody.make(
            T.OperationType.SET_OPTIONS,
            T.SetOptionsOp.make(
                inflationDest=None, clearFlags=None, setFlags=None,
                masterWeight=None, lowThreshold=None, medThreshold=None,
                highThreshold=None, homeDomain=None,
                signer=T.Signer.make(
                    key=T.SignerKey.make(
                        T.SignerKeyType.SIGNER_KEY_TYPE_ED25519,
                        signer_key),
                    weight=1))))
    assert app.herder.recv_transaction(
        lg._sign_tx(lg.accounts[0], [op], 100)) == 0
    app.herder.manual_close()
    envs = lg.generate_payments(16)
    assert sum(1 for e in envs
               if app.herder.recv_transaction(e) == 0) == len(envs)
    app.herder.manual_close()
    stats = dict(app.parallel_apply.stats)
    assert stats["native_declines"] > 0, stats
    breakout = {name: m.count
                for name, m in app.metrics._metrics.items()
                if name.startswith("apply.native.decline.")}
    assert sum(breakout.values()) == stats["native_declines"], \
        (breakout, stats["native_declines"])
    assert any("unsupported_account_shape" in name
               for name in breakout), breakout
    app.graceful_stop()


# -- metrics / observability -------------------------------------------------

def test_native_counters_reach_metrics_and_stats_line(tmp_path):
    stats_file = str(tmp_path / "apply_stats.jsonl")
    app = _mk_app(2, PARALLEL_APPLY_STATS_FILE=stats_file)
    lg = LoadGenerator(app)
    lg.payment_pattern = "pairs"
    lg.create_accounts(20)
    envs = lg.generate_payments(40)
    assert sum(1 for e in envs
               if app.herder.recv_transaction(e) == 0) == 40
    fps = []
    _close_and_fingerprint(app, fps)
    stats = dict(app.parallel_apply.stats)
    assert stats["native_hits"] > 0
    assert app.metrics.counter("apply.native.hit").count == \
        stats["native_hits"]
    app.graceful_stop()
    import json

    with open(stats_file) as f:
        line = json.loads(f.readline())
    assert line["native_hits"] == stats["native_hits"]
    assert line["native"] is True


def test_native_cluster_spans_reach_the_trace_endpoint():
    import json

    from stellar_core_tpu.main.http_server import CommandHandler

    app = _mk_app(2)
    lg = LoadGenerator(app)
    lg.payment_pattern = "pairs"
    lg.create_accounts(20)
    envs = lg.generate_payments(40)
    assert sum(1 for e in envs
               if app.herder.recv_transaction(e) == 0) == 40
    fps = []
    _close_and_fingerprint(app, fps)
    seq = app.ledger_manager.last_closed_seq()
    handler = CommandHandler(app)
    code, body = handler.handle("trace", {"ledger": str(seq)})
    assert code == 200
    trace = json.loads(body.data.decode())
    # small kernel-eligible clusters coalesce into batched crossings
    # (ROADMAP 2d); a lone trailing cluster still spans per-cluster
    native_events = [e for e in trace["traceEvents"]
                     if e["name"] in ("ledger.apply.cluster.native",
                                      "ledger.apply.cluster.native.batch")]
    assert native_events, "no native cluster spans in the close trace"
    assert all(e["args"].get("outcome") == "hit" for e in native_events)
    batch_events = [e for e in native_events
                    if e["name"].endswith(".batch")]
    assert batch_events, "expected a batched kernel crossing"
    assert all(e["args"]["clusters"] >= 2 for e in batch_events)
    assert app.parallel_apply.stats["batched_clusters"] >= 2
    app.graceful_stop()


# -- the pre-pack host screen ------------------------------------------------

def test_account_screen_declines_before_packing():
    """The persistent account-shape declines (extra signers, inflation
    destination) are caught on the decoded snapshot entry BEFORE the
    cluster pays the snapshot/book encode — same refusal the kernel's
    parse would raise post-pack, minus the per-close packing tax."""
    from types import SimpleNamespace

    from stellar_core_tpu.apply.native_apply import (KernelDecline,
                                                     _screen_account)
    from stellar_core_tpu.crypto import sha256
    from stellar_core_tpu.ledger.ledger_txn import account_key_bytes
    from stellar_core_tpu.transactions import utils as U

    aid = b"\x11" * 32
    kb = account_key_bytes(aid)
    snapshot = SimpleNamespace(
        store={kb: U.make_account_entry(aid, 500, seq_num=1)})
    _screen_account(snapshot, aid, 0)  # clean shape: no refusal
    _screen_account(snapshot, b"\x99" * 32, 0)  # absent: kernel's call

    signer = T.Signer.make(
        key=T.SignerKey.make(T.SignerKeyType.SIGNER_KEY_TYPE_ED25519,
                             sha256(b"screen-signer")),
        weight=1)
    snapshot.store[kb] = U.make_account_entry(
        aid, 500, seq_num=1, signers=[signer])
    with pytest.raises(KernelDecline, match="unsupported account shape"):
        _screen_account(snapshot, aid, 3)

    snapshot.store[kb] = U.make_account_entry(
        aid, 500, seq_num=1, inflationDest=T.account_id(b"\x22" * 32))
    with pytest.raises(KernelDecline, match="unsupported account shape"):
        _screen_account(snapshot, aid, 3)


# -- the packed-value tier ---------------------------------------------------

def test_packed_entry_encodes_without_decode_and_decodes_on_touch():
    from stellar_core_tpu.ledger.packed import LazyUnion, PackedEntry
    from stellar_core_tpu.transactions import utils as U

    entry = U.make_account_entry(b"\x07" * 32, 12345, seq_num=99)
    eb = T.LedgerEntry.encode(entry)
    pe = PackedEntry(eb)
    # encode path: memo hit, no field materialization
    assert T.LedgerEntry.encode(pe) == eb
    assert "data" not in pe.__dict__
    # field access materializes once and matches the decoded value
    assert pe.data.value.balance == 12345
    assert pe.lastModifiedLedgerSeq == entry.lastModifiedLedgerSeq
    assert pe._replace(lastModifiedLedgerSeq=7).lastModifiedLedgerSeq == 7

    meta = T.TransactionMeta.make(2, T.TransactionMetaV2.make(
        txChangesBefore=[], operations=[], txChangesAfter=[]))
    mb = T.TransactionMeta.encode(meta)
    lazy = LazyUnion(T.TransactionMeta, mb)
    assert T.TransactionMeta.encode(lazy) == mb
    assert lazy.type == 2
    assert lazy.value.operations == []


# -- PYTHONHASHSEED variation (subprocess) -----------------------------------

_HASHSEED_WORKER = """
import hashlib
import sys

sys.path.insert(0, {repo!r})
from tests.test_apply_determinism import _run_mixed_workload
from tests.txtest import native_unbuilt

if {native}:
    fps = _run_mixed_workload()
else:
    with native_unbuilt():
        fps = _run_mixed_workload()
for lh, bh, meta in fps:
    print(lh.hex(), bh.hex(), hashlib.sha256(meta).hexdigest())
"""


@pytest.mark.slow
def test_native_close_bit_identical_under_hashseed_variation():
    """Mixed workload with the kernel engaged under PYTHONHASHSEED 0 vs
    4242, cross-checked against a run on a host without the kernel: all
    three must produce the same per-close fingerprints."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    outputs = []
    for seed, native in (("0", True), ("4242", True), ("0", False)):
        env = dict(os.environ)
        env["PYTHONHASHSEED"] = seed
        env["JAX_PLATFORMS"] = "cpu"
        env["PARALLEL_APPLY_WORKERS"] = "2"
        proc = subprocess.run(
            [sys.executable, "-c", _HASHSEED_WORKER.format(
                repo=repo, native=native)],
            capture_output=True, text=True, cwd=repo, env=env,
            timeout=600)
        assert proc.returncode == 0, proc.stderr[-4000:]
        lines = proc.stdout.strip().splitlines()
        assert len(lines) >= 8, proc.stdout
        outputs.append(lines)
    assert outputs[0] == outputs[1], \
        "native close fingerprints diverged across hash seeds"
    assert outputs[0] == outputs[2], \
        "native close fingerprints diverged from the Python reference"


# -- detlint scope (satellite) -----------------------------------------------

def test_detlint_covers_native_apply_and_kernel_handle():
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from tools.lint.engine import CONSENSUS_DIRS, REPO, _parse_file

    assert "apply" in CONSENSUS_DIRS  # native_apply.py rides the scope
    # the kernel handle in the native loader must stay lock-annotated:
    # detlint's guarded-by audit only bites on annotated fields
    rel = "stellar_core_tpu/native/__init__.py"
    with open(os.path.join(REPO, rel)) as f:
        info = _parse_file(rel, f.read())
    guarded = set()
    for line, lock in info.guards.items():
        text = info.line_text(line)
        guarded.add(text.split("=")[0].strip().split(":")[0].strip())
    assert "_applykernel_mod" in guarded, guarded
    assert "_applykernel_tried" in guarded, guarded


# -- post-apply invariant pass over kernel deltas (ISSUE 7 satellite) --------

def test_native_invariant_pass_arms_with_checks_configured():
    """INVARIANT_CHECKS configured (test_config defaults to [".*"])
    must arm the post-apply cluster-delta pass whenever the kernel can
    engage; an empty checker list must not (the lazy-decode opt-out)."""
    app = _mk_app(2)
    assert app.parallel_apply.native_invariants is True
    app.graceful_stop()
    app = _mk_app(2, INVARIANT_CHECKS=[])
    assert app.parallel_apply.native_invariants is False
    app.graceful_stop()


def test_native_cluster_invariant_violation_aborts_to_python():
    """A violation seen ONLY at cluster granularity (frame is None —
    modeling a kernel-side divergence the per-op Python path does not
    reproduce) must abort the parallel attempt; the sequential replay's
    bytes win and the close completes bit-identical to the Python
    reference."""
    from stellar_core_tpu.invariant.manager import Invariant

    class NativeOnlyTrip(Invariant):
        NAME = "NativeOnlyTrip"

        def check_on_tx_apply(self, ltx, frame, ok):
            return "tripped on a kernel delta" if frame is None else ""

    with native_unbuilt():
        seq, _ = _run_workload(2, n_closes=2)
    apps = []

    def arm(app):
        app.invariants.invariants.append(NativeOnlyTrip())
        apps.append(app)

    fps, stats = _run_workload(2, n_closes=2, app_hook=arm)
    _assert_identical(seq, fps, "native invariant abort")
    assert stats["aborts"] > 0, stats
    assert apps[0].metrics.counter(
        "apply.native.invariant-fail").count > 0


def test_native_invariant_violation_reproduced_crashes_close():
    """When the sequential replay REPRODUCES the violation it is a real
    bug, not kernel divergence: the close must crash safety-first."""
    from stellar_core_tpu.invariant.manager import (
        Invariant, InvariantDoesNotHold)

    class AlwaysTrip(Invariant):
        NAME = "AlwaysTrip"

        def check_on_tx_apply(self, ltx, frame, ok):
            return "always fails"

    app = _mk_app(2)
    lg = LoadGenerator(app)
    lg.create_accounts(10)
    envs = lg.generate_payments(5)
    for env in envs:
        assert app.herder.recv_transaction(env) == 0
    app.invariants.invariants.append(AlwaysTrip())
    with pytest.raises(InvariantDoesNotHold):
        app.herder.manual_close()
    app.graceful_stop()
