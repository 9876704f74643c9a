"""Catchup of upstream's ledger range, ``catchup <to>/<count>``, and the
replay's checkpoint-batched signature verification
(ref src/catchup/CatchupRange.cpp, ApplyCheckpointWork.cpp).

The archive is published by a host-backend node at the accelerated
checkpoint frequency (8): ledger 2 creates the accounts, every later
ledger carries one payment per account, and one tx per ledger has its
signature forged (one bit of S flipped), written into the ledger's tx
set by a direct close so that the archive records txBAD_AUTH for it.
"""
import json
from types import SimpleNamespace

import pytest

from stellar_core_tpu.catchup import CatchupConfiguration, CatchupWork
from stellar_core_tpu.catchup.catchup_work import ApplyCheckpointsWork
from stellar_core_tpu.crypto import SecretKey, sha256
from stellar_core_tpu.herder import tx_set as tx_set_mod
from stellar_core_tpu.herder.tx_set import TxSetFrame
from stellar_core_tpu.history.manager import HistoryManager
from stellar_core_tpu.ledger.ledger_manager import LedgerCloseData
from stellar_core_tpu.main import Application
from stellar_core_tpu.main.config import test_config as node_config
from stellar_core_tpu.main.command_line import main as cli_main
from stellar_core_tpu.transactions.frame import TC, tx_frame_from_envelope
from stellar_core_tpu.transactions.signature_checker import signature_hint
from stellar_core_tpu.utils.clock import ClockMode, VirtualClock
from stellar_core_tpu.work.work import State
from stellar_core_tpu.xdr import types as T

N_ACCOUNTS = 20
LAST_LEDGER = 31          # checkpoints 7, 15, 23 and 31 published


class _HM:
    """The checkpoint arithmetic of a node at the real frequency (64)."""
    app = SimpleNamespace(config=SimpleNamespace(
        ARTIFICIALLY_ACCELERATE_TIME_FOR_TESTING=False))
    checkpoint_frequency = HistoryManager.checkpoint_frequency
    is_last_ledger_in_checkpoint = HistoryManager.is_last_ledger_in_checkpoint
    checkpoint_containing = HistoryManager.checkpoint_containing
    first_ledger_in_checkpoint = HistoryManager.first_ledger_in_checkpoint
    latest_checkpoint_at_or_before = \
        HistoryManager.latest_checkpoint_at_or_before


@pytest.mark.parametrize("to,count,lcl,want", [
    (127, 64, 1, (63, 64)),       # upstream's example: buckets at 63
    (127, 40, 1, (63, 64)),       # to - count = 87 lies in 64-127
    (100, 10, 1, (63, 64)),       # to - count = 90, not a checkpoint
    (191, 64, 1, (127, 128)),
    (127, 126, 1, (None, 2)),     # count reaches genesis: full replay
    (127, 100, 1, (None, 2)),     # first ledger in the first checkpoint
    (127, 0, 1, (127, 128)),      # buckets only
    (130, 0, 1, (127, 128)),      # buckets, then the tail to 130
    (191, 64, 70, (None, 71)),    # a node past genesis replays its gap
])
def test_count_plans_upstream_range(to, count, lcl, want):
    assert CatchupConfiguration(to, count=count).plan(lcl, _HM()) == want


@pytest.mark.parametrize("mode,want", [
    (CatchupConfiguration.MINIMAL, (127, 128)),
    (CatchupConfiguration.COMPLETE, (None, 2)),
])
def test_mode_keeps_its_meaning(mode, want):
    assert CatchupConfiguration(130, mode).plan(1, _HM()) == want


def test_negative_count_refused():
    with pytest.raises(ValueError):
        CatchupConfiguration(127, count=-1)


# -- an archive with forged rows ----------------------------------------------

def make_node(tmp_path, name, archive_dir, **kw):
    node_dir = tmp_path / name
    node_dir.mkdir()
    app = Application(VirtualClock(ClockMode.VIRTUAL_TIME), node_config(
        HISTORY_ARCHIVES=[("test", str(archive_dir))],
        DATABASE=str(node_dir / "node.db"),
        BUCKET_DIR_PATH_REAL=str(node_dir / "buckets"), **kw))
    app.start()
    return app


def envelope(nid, key, seq, ops, forge=False):
    tx = T.Transaction.make(
        sourceAccount=T.muxed_account(key.public_key().raw), fee=100 * len(ops),
        seqNum=seq, cond=T.Preconditions.make(T.PreconditionType.PRECOND_NONE),
        memo=T.MEMO_NONE_VALUE, operations=ops,
        ext=T.Transaction.fields[6][1].make(0))
    payload = T.TransactionSignaturePayload.make(
        networkId=nid,
        taggedTransaction=T.TransactionSignaturePayload.fields[1][1].make(
            T.EnvelopeType.ENVELOPE_TYPE_TX, tx))
    sig = key.sign(sha256(T.TransactionSignaturePayload.encode(payload)))
    if forge:
        sig = sig[:40] + bytes([sig[40] ^ 0x10]) + sig[41:]
    return T.TransactionEnvelope.make(
        T.EnvelopeType.ENVELOPE_TYPE_TX, T.TransactionV1Envelope.make(
            tx=tx, signatures=[T.DecoratedSignature.make(
                hint=signature_hint(key.public_key().raw), signature=sig)]))


def op(body_type, value):
    return T.Operation.make(sourceAccount=None,
                            body=T.OperationBody.make(body_type, value))


def close(app, envs):
    lm = app.ledger_manager
    nid = app.config.network_id()
    tx_set = TxSetFrame(nid, lm.last_closed_hash(),
                        [tx_frame_from_envelope(nid, e) for e in envs])
    sv = T.StellarValue.make(
        txSetHash=tx_set.contents_hash(),
        closeTime=lm.last_closed_header().scpValue.closeTime + 5,
        upgrades=[], ext=T.StellarValue.fields[3][1].make(
            T.StellarValueType.STELLAR_VALUE_BASIC))
    lm.close_ledger(LedgerCloseData(lm.last_closed_seq() + 1, tx_set, sv))


@pytest.fixture(scope="module")
def archive(tmp_path_factory):
    """(archive dir, {seq: header row}, {seq: [(txid, txresult)]})."""
    tmp = tmp_path_factory.mktemp("range")
    arch_dir = tmp / "archive"
    app = make_node(tmp, "publisher", arch_dir)
    nid = app.config.network_id()
    root = SecretKey(nid)
    keys = [SecretKey(sha256(b"range-acct-%d" % i))
            for i in range(N_ACCOUNTS)]
    close(app, [envelope(nid, root, 1, [
        op(T.OperationType.CREATE_ACCOUNT, T.CreateAccountOp.make(
            destination=T.account_id(k.public_key().raw),
            startingBalance=10**9)) for k in keys])])
    while app.ledger_manager.last_closed_seq() < LAST_LEDGER:
        seq = app.ledger_manager.last_closed_seq() + 1
        close(app, [envelope(
            nid, k, (2 << 32) + seq - 2,
            [op(T.OperationType.PAYMENT, T.PaymentOp.make(
                destination=T.muxed_account(
                    keys[(i + 1) % N_ACCOUNTS].public_key().raw),
                asset=T.Asset.make(T.AssetType.ASSET_TYPE_NATIVE),
                amount=1 + seq + i))],
            forge=i == seq % N_ACCOUNTS) for i, k in enumerate(keys)])
    app.ledger_manager.pipeline.drain()
    db = app.database
    headers = dict(db.execute("SELECT ledgerseq, data FROM ledgerheaders"))
    results = {}
    for seq, txid, res in db.execute(
            "SELECT ledgerseq, txid, txresult FROM txhistory "
            "ORDER BY ledgerseq, txindex"):
        results.setdefault(seq, []).append((bytes(txid), bytes(res)))
    app.graceful_stop()
    return arch_dir, headers, results


def result_code(txresult: bytes) -> int:
    return int.from_bytes(txresult[40:44], "big", signed=True)


def catch_up(app, to, count):
    work = CatchupWork(app, app.history_manager.archives[0],
                       CatchupConfiguration(to, count=count))
    app.work_scheduler.schedule(work)
    for _ in range(5000):
        app.work_scheduler.crank()
        if work.state not in (State.RUNNING, State.WAITING):
            break
    return work


def replayed(app, first, last):
    db = app.database
    headers = {s: bytes(d) for s, d in db.execute(
        "SELECT ledgerseq, data FROM ledgerheaders WHERE ledgerseq "
        "BETWEEN ? AND ?", (first, last))}
    results = {}
    for seq, txid, res in db.execute(
            "SELECT ledgerseq, txid, txresult FROM txhistory "
            "ORDER BY ledgerseq, txindex"):
        results.setdefault(seq, []).append((bytes(txid), bytes(res)))
    return headers, results


@pytest.fixture
def device_calls(monkeypatch):
    """Every call of the shared device leg, by its number of rows."""
    calls = []
    leg = tx_set_mod.verify_signature_batch

    def counted(triples, tracer=None):
        calls.append(len(triples))
        return leg(triples, tracer)
    monkeypatch.setattr(tx_set_mod, "verify_signature_batch", counted)
    return calls


@pytest.mark.parametrize("path", ["batched", "per_ledger", "host"])
def test_replay_paths_agree_with_the_archive(tmp_path, archive, path,
                                             device_calls, monkeypatch):
    """catchup 31/12: buckets at 15, then 16-31 replayed, two
    checkpoints.  Device replay (JAX's CPU backend here), batched per
    checkpoint or per ledger, and host replay store the archive's
    headers and results, forged rows as txBAD_AUTH with their fee."""
    arch_dir, want_headers, want_results = archive
    if path == "per_ledger":
        monkeypatch.setattr(ApplyCheckpointsWork, "_verify_checkpoint",
                            lambda self, seq: True)
    app = make_node(tmp_path, "joiner", arch_dir,
                    CRYPTO_BACKEND="cpu" if path == "host" else "tpu")
    try:
        work = catch_up(app, 31, 12)
        assert work.state == State.SUCCESS
        assert app.ledger_manager.last_closed_seq() == 31
        headers, results = replayed(app, 16, 31)
        counters = {k: app.metrics.counter(
            f"catchup.replay.{k}").count
            for k in ("sig_dispatches", "sigs_batched")}
    finally:
        app.graceful_stop()
    assert headers == {s: bytes(want_headers[s]) for s in range(16, 32)}
    assert results == {s: want_results[s] for s in range(16, 32)}
    forged = [res for s in range(16, 32) for _, res in results[s]
              if result_code(res) != TC.txSUCCESS]
    assert len(forged) == 16
    assert all(result_code(r) == TC.txBAD_AUTH for r in forged)
    assert all(int.from_bytes(r[32:40], "big") == 100 for r in forged)
    if path == "batched":
        assert device_calls == [8 * N_ACCOUNTS] * 2
        assert counters == {"sig_dispatches": 2,
                            "sigs_batched": 16 * N_ACCOUNTS}
    elif path == "per_ledger":
        assert device_calls == [N_ACCOUNTS] * 16
        assert counters == {"sig_dispatches": 0, "sigs_batched": 0}
    else:
        assert device_calls == []


@pytest.mark.parametrize("to,count,first,dispatches", [
    (23, 8, 16, 1),       # one checkpoint, buckets at 15
    (20, 5, 16, 1),       # to - count = 15 on a checkpoint; a partial one
    (21, 3, 16, 1),       # to - count = 18 inside 16-23
    (13, 12, 2, 2),       # count reaches genesis: replay 2-13
])
def test_count_range_end_to_end(tmp_path, archive, to, count, first,
                                dispatches):
    """The range a count picks, on a device-backend joiner: the first
    ledger it replays, one dispatch per replayed checkpoint, and the
    archive's header at ``to``."""
    arch_dir, want_headers, _ = archive
    app = make_node(tmp_path, "joiner", arch_dir, CRYPTO_BACKEND="tpu")
    try:
        work = catch_up(app, to, count)
        assert work.state == State.SUCCESS
        lm = app.ledger_manager
        assert lm.last_closed_seq() == to
        stored = {s for (s,) in app.database.execute(
            "SELECT ledgerseq FROM ledgerheaders")}
        assert set(range(first, to + 1)) <= stored
        assert app.metrics.counter(
            "catchup.replay.sig_dispatches").count == dispatches
        assert app.metrics.counter(
            "catchup.ledger.replayed").count == to - first + 1
        assert bytes(app.database.execute(
            "SELECT data FROM ledgerheaders WHERE ledgerseq=?",
            (to,)).fetchone()[0]) == bytes(want_headers[to])
    finally:
        app.graceful_stop()


def test_command_line_range(tmp_path, archive, capsys):
    """``catchup 23/8`` from the command line: buckets at 15, 16-23
    replayed, the archive's header at 23."""
    arch_dir, want_headers, _ = archive
    conf = tmp_path / "node.toml"
    conf.write_text(f"""
run_standalone = true
artificially_accelerate_time_for_testing = true
deferred_gc = false
slow_close_threshold_seconds = 0.0
crypto_backend = "cpu"
scp_tally_backend = "host"
database = "{tmp_path / 'node.db'}"
history_archives = [["test", "{arch_dir}"]]
""")
    assert cli_main(["--conf", str(conf), "catchup", "23/8"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["state"] == "SUCCESS" and out["ledger"] == 23
    from stellar_core_tpu.xdr import xdr_sha256

    assert out["hash"] == xdr_sha256(
        T.LedgerHeader, T.LedgerHeader.decode(want_headers[23])).hex()


def test_herder_close_dispatches_its_own_batch(tmp_path, archive,
                                               device_calls):
    """A herder close passes no verdicts: phase 0 dispatches the set."""
    arch_dir, _, _ = archive
    app = make_node(tmp_path, "validator", arch_dir, CRYPTO_BACKEND="tpu")
    try:
        nid = app.config.network_id()
        root = SecretKey(nid)
        dest = SecretKey(sha256(b"range-herder-dest")).public_key().raw
        assert app.herder.recv_transaction(envelope(nid, root, 1, [
            op(T.OperationType.CREATE_ACCOUNT, T.CreateAccountOp.make(
                destination=T.account_id(dest),
                startingBalance=10**9))])) == 0
        calls_before = len(device_calls)
        app.herder.manual_close()
        assert device_calls[calls_before:] == [1]
    finally:
        app.graceful_stop()
