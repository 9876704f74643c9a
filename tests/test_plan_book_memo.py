"""The parallel-apply planner's memo of resting offers' plan-time
derivations (``apply/footprint.py`` ``OfferMemo``): a plan built with a
memo kept across closes equals one built from scratch — clusters, their
keys and writes, and every book direction's rows in order — through
closes that create, partly fill, reprice, delete and re-sponsor resting
offers, and through a plan taken while a sealed close is still
uncommitted.  The memo stays bounded by the live book, and the node
reports how much of each plan's book it served.
"""
import random

from stellar_core_tpu.apply.footprint import OfferMemo
from stellar_core_tpu.apply.planner import plan_parallel_apply
from stellar_core_tpu.ledger.ledger_txn import LedgerTxn, account_key_bytes
from stellar_core_tpu.main import Application
from stellar_core_tpu.main import test_config as _test_config
from stellar_core_tpu.simulation.load_generator import LoadGenerator
from stellar_core_tpu.transactions import sponsorship as SP
from stellar_core_tpu.transactions import utils as U
from stellar_core_tpu.transactions.frame import tx_frame_from_envelope
from stellar_core_tpu.utils.clock import ClockMode, VirtualClock
from stellar_core_tpu.xdr import types as T

from .txtest import BASE_FEE, NETWORK_ID, TestLedger


def _frames(*envs):
    return [tx_frame_from_envelope(NETWORK_ID, env) for env in envs]


def _sell(acct, selling, buying, amount, pn, pd=100, offer_id=0,
          source=None):
    return acct.op(T.OperationType.MANAGE_SELL_OFFER,
                   T.ManageSellOfferOp.make(
                       selling=selling, buying=buying, amount=amount,
                       price=T.Price.make(n=pn, d=pd), offerID=offer_id),
                   source=source)


def _offer_key(seller, offer_id):
    LE = T.LedgerEntryType
    return T.LedgerKey.make(LE.OFFER, T.LedgerKey.arms[LE.OFFER][1].make(
        sellerID=T.account_id(seller), offerID=offer_id))


class _Market:
    """A TestLedger with a LOAD asset, sellers resting LOAD-for-native
    offers, takers crossing them and two sponsors; ``close`` applies a
    list of envelopes as one ledger, committed or staged as a sealed,
    uncommitted close."""

    def __init__(self):
        self.lg = TestLedger()
        root = self.lg.root()
        self.issuer = root.create("memo-issuer", 10**10)
        self.load = U.make_asset(b"LOAD", self.issuer.account_id)
        self.xlm = U.asset_native()
        self.sellers = [root.create(f"memo-seller{i}", 10**10)
                        for i in range(6)]
        self.takers = [root.create(f"memo-taker{i}", 10**10)
                       for i in range(2)]
        self.sponsors = [root.create(f"memo-sponsor{i}", 10**10)
                         for i in range(2)]
        self.payer = root.create("memo-payer", 10**10)
        self.payee = root.create("memo-payee", 10**10)
        for a in self.sellers + self.takers:
            a.apply(a.tx([a.op_change_trust(self.load)]))
            self.issuer.apply(self.issuer.tx([self.issuer.op_payment(
                a.account_id, 10**8, asset=self.load)]))
        self.staged = None

    def close(self, envs, pending=False):
        """Apply ``envs`` in one LedgerTxn; commit it, or stage it on
        the root as the close pipeline does for a sealed close whose SQL
        commit is still running."""
        root = self.lg.root_txn
        frames = _frames(*envs)
        ltx = LedgerTxn(root)
        hdr = ltx.header()
        ltx.set_header(hdr._replace(ledgerSeq=hdr.ledgerSeq + 1))
        for f in frames:
            f.process_fee_seq_num(ltx, base_fee=BASE_FEE)
        for f in frames:
            ok, result, _meta = f.apply(ltx)
            assert ok, result
        if not pending:
            ltx.commit()
            return
        delta, header = ltx._delta, ltx.header()
        ltx.rollback()
        root.stage_sealed(delta, header)
        self.staged = (delta, header)

    def finish_pending(self):
        root = self.lg.root_txn
        delta, header = self.staged
        root.commit_pending_sql(delta, header)
        root.db.commit()
        root.clear_pending()
        self.staged = None

    def offers(self):
        """(seller, offerID) of every resting offer, by the SQL index."""
        return self.lg.db.execute(
            "SELECT sellerid, offerid FROM offers ORDER BY offerid"
        ).fetchall()

    def plan_set(self):
        """A set whose plan materializes the LOAD/native book (a taker's
        crossing offer) beside an unrelated payment, so it plans into
        two clusters."""
        t = self.takers[0]
        return _frames(
            t.tx([_sell(t, self.xlm, self.load, 1000, 100)]),
            self.payer.tx([self.payer.op_payment(
                self.payee.account_id, 5)]))


def _plan(market, frames, memo):
    with LedgerTxn(market.lg.root_txn) as ltx:
        plan, stats = plan_parallel_apply(frames, ltx, memo=memo)
        ltx.rollback()
    return plan, stats


def _view(plan):
    """Everything a plan hands the executor, in order."""
    clusters = [(c.indices, sorted(c.keys), sorted(c.writes),
                 sorted(c.pairs), c.writes_header) for c in plan.clusters]
    books = [(pair, sorted(mat.keys), sorted(mat.read_keys),
              sorted(mat.offers.items()))
             for pair, mat in sorted(plan.context.books.items())]
    return clusters, books


def _plan_with(market, memo):
    """Plan the market's set with ``memo`` (ending the plan as the
    node's manager does) and from scratch; assert they agree; return the
    memo plan's stats."""
    frames = market.plan_set()
    plan, stats = _plan(market, frames, memo)
    memo.end_plan()
    fresh, fresh_stats = _plan(market, frames, OfferMemo())
    assert plan is not None and fresh is not None, (stats, fresh_stats)
    assert _view(plan) == _view(fresh)
    assert stats["book_rows"] == fresh_stats["book_rows"]
    assert fresh_stats["book_rows_reused"] == 0
    return stats


def _grouped(acct_ops):
    """One tx per account holding its ops, in first-seen order (one
    sequence number per account and close)."""
    ops = {}
    for acct, op in acct_ops:
        ops.setdefault(acct.account_id, (acct, []))[1].append(op)
    return [acct.tx(acct_list) for acct, acct_list in ops.values()]


def _rest_offers(market, rng, n):
    return [(s, _sell(s, market.load, market.xlm,
                      50_000 + rng.randrange(50_000),
                      101 + rng.randrange(40)))
            for s in (market.sellers[k % len(market.sellers)]
                      for k in range(n))]


def _delete(acct, market, offer_id):
    return acct, _sell(acct, market.load, market.xlm, 0, 150,
                       offer_id=offer_id)


def test_memo_plan_equals_fresh_plan_over_closes():
    m = _Market()
    rng = random.Random(5)
    memo = OfferMemo()
    m.close(_grouped(_rest_offers(m, rng, 12)))
    first = _plan_with(m, memo)
    assert first["book_rows"] == 12 and first["book_rows_reused"] == 0

    # partly fill the cheapest offers: their blobs change
    t = m.takers[1]
    m.close([t.tx([_sell(t, m.xlm, m.load, 120_000, 100, pd=140)])])
    stats = _plan_with(m, memo)
    assert 0 < stats["book_rows_reused"] < stats["book_rows"]

    # reprice one resting offer and delete two others
    offers = m.offers()
    by_seller = {s.account_id: s for s in m.sellers}
    (s0, id0), (s1, id1), (s2, id2) = offers[-3:]
    a, b, c = by_seller[s0], by_seller[s1], by_seller[s2]
    m.close(_grouped([
        (a, _sell(a, m.load, m.xlm, 70_000, 199, offer_id=id0)),
        _delete(b, m, id1), _delete(c, m, id2)]))
    stats = _plan_with(m, memo)
    assert stats["book_rows"] == len(offers) - 2
    assert stats["book_rows_reused"] > 0

    # a sponsored resting offer, then its sponsorship moves to another
    # sponsor: same key, same price, same amount, another sponsor key
    sp0, sp1 = m.sponsors
    s = m.sellers[0]
    m.close([sp0.tx([
        sp0.op_begin_sponsoring(s.account_id),
        _sell(sp0, m.load, m.xlm, 40_000, 160, source=s.account_id),
        sp0.op_end_sponsoring(source=s.account_id),
    ], extra_signers=[s.secret])])
    _plan_with(m, memo)
    oid = max(i for _, i in m.offers())
    okey = _offer_key(s.account_id, oid)
    assert SP.entry_sponsor(s.entry(okey)) == sp0.account_id
    m.close([sp0.tx([
        sp1.op_begin_sponsoring(sp0.account_id, source=sp1.account_id),
        sp0.op_revoke_sponsorship_key(okey),
        sp1.op_end_sponsoring(source=sp0.account_id),
    ], extra_signers=[sp1.secret])])
    assert SP.entry_sponsor(s.entry(okey)) == sp1.account_id
    stats = _plan_with(m, memo)
    assert stats["book_rows_reused"] == stats["book_rows"] - 1


def test_memo_keyed_by_key_alone_misses_a_moved_sponsorship():
    """The sponsorship move above is the case a memo keyed by key bytes
    alone gets wrong: its plan keeps the old sponsor's account."""

    class KeyOnlyMemo(OfferMemo):
        def get(self, key):
            return super().get((key[0], b""))

        def put(self, key, derivation):
            super().put((key[0], b""), derivation)

    m = _Market()
    sp0, sp1 = m.sponsors
    s = m.sellers[0]
    m.close([sp0.tx([
        sp0.op_begin_sponsoring(s.account_id),
        _sell(sp0, m.load, m.xlm, 40_000, 160, source=s.account_id),
        sp0.op_end_sponsoring(source=s.account_id),
    ], extra_signers=[s.secret])])
    memo = KeyOnlyMemo()
    _plan(m, m.plan_set(), memo)
    memo.end_plan()
    okey = _offer_key(s.account_id, m.offers()[0][1])
    m.close([sp0.tx([
        sp1.op_begin_sponsoring(sp0.account_id, source=sp1.account_id),
        sp0.op_revoke_sponsorship_key(okey),
        sp1.op_end_sponsoring(source=sp0.account_id),
    ], extra_signers=[sp1.secret])])
    stale, _ = _plan(m, m.plan_set(), memo)
    fresh, _ = _plan(m, m.plan_set(), OfferMemo())
    assert _view(stale) != _view(fresh)
    (_pair, mat), = fresh.context.books.items()
    (_pair, stale_mat), = stale.context.books.items()
    assert account_key_bytes(sp1.account_id) in mat.keys
    assert account_key_bytes(sp1.account_id) not in stale_mat.keys


def test_memo_plan_while_a_sealed_close_is_uncommitted():
    m = _Market()
    rng = random.Random(9)
    memo = OfferMemo()
    m.close(_grouped(_rest_offers(m, rng, 10)))
    _plan_with(m, memo)
    # a sealed close that creates, fills and deletes offers, staged on
    # the root's write-ahead overlay while its SQL commit "runs"
    offers = m.offers()
    by_seller = {s.account_id: s for s in m.sellers}
    sid, oid = offers[-1]
    d = by_seller[sid]
    t = m.takers[1]
    m.close(_grouped(_rest_offers(m, rng, 3) + [
        _delete(d, m, oid),
        (t, _sell(t, m.xlm, m.load, 60_000, 100, pd=140))]), pending=True)
    assert m.lg.root_txn._pending_offers
    stats = _plan_with(m, memo)
    assert stats["book_rows_reused"] > 0
    m.finish_pending()
    stats = _plan_with(m, memo)
    assert stats["book_rows"] == len(m.offers())
    assert stats["book_rows_reused"] > 0


def test_memo_shrinks_to_the_live_book():
    m = _Market()
    rng = random.Random(13)
    memo = OfferMemo()
    m.close(_grouped(_rest_offers(m, rng, 16)))
    _plan_with(m, memo)
    assert len(memo) == 16
    by_seller = {s.account_id: s for s in m.sellers}
    m.close(_grouped([_delete(by_seller[sid], m, oid)
                      for sid, oid in m.offers()[::2]]))
    live = len(m.offers())
    assert live == 8
    # two generations: the next plan still keeps the previous one's
    # entries, the one after holds the live book alone
    _plan_with(m, memo)
    assert len(memo) == 16
    stats = _plan_with(m, memo)
    assert len(memo) == live
    assert stats["book_rows"] == stats["book_rows_reused"] == live


def _mk_node():
    app = Application(VirtualClock(ClockMode.VIRTUAL_TIME), _test_config(
        TESTING_UPGRADE_MAX_TX_SET_SIZE=300, PARALLEL_APPLY_WORKERS=2))
    app.start()
    return app


def test_node_reports_book_rows_reused_per_close():
    """A fresh node's first plan over a book reuses nothing, the next
    one reuses; each close's ``parallel`` phase and the node's counters
    carry the numbers."""
    app = _mk_node()
    try:
        lg = LoadGenerator(app)
        lg.create_accounts(20)
        lg.setup_dex()
        rng = random.Random(3)
        seen = []
        for _ in range(5):
            envs = [lg.offer_envelope(
                lg.accounts[i], 5 + rng.randrange(50),
                90 + rng.randrange(20), 100,
                selling=lg.dex_asset, buying=U.asset_native())
                for i in range(10)]
            envs += [lg.payment_envelope(
                lg.accounts[i], lg.accounts[i + 1].public_key().raw, 7)
                for i in range(10, 20, 2)]
            for env in envs:
                assert app.herder.recv_transaction(env) == 0
            app.herder.manual_close()
            par = app.ledger_manager.last_close_phases["parallel"]
            seen.append((par["book_rows"], par["book_rows_reused"]))
        with_book = [i for i, (rows, _) in enumerate(seen) if rows]
        assert len(with_book) >= 2, seen
        assert seen[with_book[0]][1] == 0, seen
        assert seen[with_book[1]][1] > 0, seen
        m = app.metrics
        assert m.counter("apply.plan.book_rows").count >= \
            sum(r for r, _ in seen) > 0
        assert m.counter("apply.plan.book_rows_reused").count >= \
            sum(u for _, u in seen) > 0
    finally:
        app.graceful_stop()
