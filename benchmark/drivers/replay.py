"""Catchup replay of a history range (config ``catchup-replay``):
upstream's ``stellar-core catchup <to>/<count>``, closed loop.

Set-up first builds the catchup ranges as ``CatchupConfiguration(to,
count=...)``: a program without ``count`` fails here, at once.  Then it
generates one archive from the seed, in the run's work directory: a
node on the reference tiers (CPU crypto, host tally, sequential apply)
closes every ledger through
``LedgerManager.close_ledger`` and publishes every checkpoint into a
local file archive.

- ledger 2: the maxTxSetSize upgrade; the root account creates the LOAD
  issuer;
- ledger 3: the root creates the accounts (CREATE_ACCOUNT, 100 a tx);
- ledger 4: every account trusts LOAD (CHANGE_TRUST);
- ledger 5: the issuer pays every account its LOAD (PAYMENT, 100 a tx);
- up to the first checkpoint: empty ledgers, so its buckets carry the
  seeded state;
- then ``replay_checkpoints`` checkpoints of MIXED batches
  (``txgen.mixed_plan``: payments and native-for-LOAD sell offers), one
  tx in ``forge_every`` with one bit of its signature's S flipped.  The
  close takes the set as it is, so the archive records txBAD_AUTH for
  that tx, its fee charged and its sequence number consumed.

It then runs a warm catchup of the first range on a fresh node, up to
its first replayed ledger: the checkpoint's dispatch builds (or loads)
the device verify program at the checkpoint's bucket.

The window is a closed loop of catchups, the ranges in turn: range k
(1 to ``replay_checkpoints``) is ``to = F(k+1) - 1`` with the config's
``range_ledgers`` as count, F being the node's checkpoint frequency, so
at 64 they are ``127/64`` and ``191/64``.  Each catchup builds a fresh
node (its own SQLite file and bucket directory) as ``cmd_run`` builds
one, and cranks ``CatchupWork`` on the node's work scheduler.  The
window ends at ``seconds``, in the middle of a catchup too; every
ledger closed by then counts.  After each close the ledger has to be in
the database, read from a second connection.

After the window, off the clock, for every catchup of the run: each
replayed ledger's stored header has to equal the archive's, byte for
byte, and each tx's stored result has to be the one the plain plan
gives (success, or txBAD_AUTH with its fee charged for a forged row);
and the state the node holds at its last ledger (every seeded account's
native balance, sequence number, subentries and LOAD balance, and the
book) has to equal what the plain reference works out from the batches
(``ledger_reference``, here with the sequence numbers and forged rows
of a chain seeded by transactions).
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import sqlite3
import time
from collections import Counter
from typing import Dict, List

from benchmark import ledger_reference, txgen
from benchmark.drivers import Annotator
from benchmark.drivers.close import make_node, result_code

TX_BAD_AUTH = -6           # TransactionResultCode
OPS_PER_TX = 100           # seeding txs carry up to this many operations
CLOSE_SECONDS = 5          # close times of the generated chain
WARM_LIMIT_S = 900         # a warm catchup still running then has failed


def _T():
    from stellar_core_tpu.xdr import types as T
    return T


def _op(kind: str, value):
    T = _T()
    return T.Operation.make(sourceAccount=None, body=T.OperationBody.make(
        getattr(T.OperationType, kind), value))


def create_account_op(dest: bytes, balance: int):
    T = _T()
    return _op("CREATE_ACCOUNT", T.CreateAccountOp.make(
        destination=T.account_id(dest), startingBalance=balance))


def change_trust_op(asset):
    T = _T()
    return _op("CHANGE_TRUST", T.ChangeTrustOp.make(
        line=T.ChangeTrustAsset.make(asset.type, asset.value),
        limit=txgen.INT64_MAX))


def chunks(items: list, n: int) -> List[list]:
    return [items[i:i + n] for i in range(0, len(items), n)]


# -- the chain, as plain data ---------------------------------------------------

def account_base_seq() -> int:
    """Every seeded account's sequence number before the first batch:
    created in ledger 3 (``3 << 32``), then its CHANGE_TRUST."""
    return (3 << 32) + 1


def issuer_state(n_accounts: int) -> tuple:
    """The issuer after seeding: funded with its payments' fees on top
    of ``BALANCE``, created in ledger 2, one tx per 100 payments."""
    return (txgen.BALANCE, (2 << 32) + len(chunks(range(n_accounts),
                                                  OPS_PER_TX)), 0, None)


def forged_index(seed: int, b: int, n: int, forge_every: int) -> set:
    """The positions of batch ``b`` whose signature is forged: one in
    ``forge_every``, offset from the seed."""
    off = int.from_bytes(txgen._h(seed, "forge", b)[:4], "big") % forge_every
    return set(range(off, n, forge_every))


def replay_batch(network_id: bytes, seed: int, n_accounts: int, n: int,
                 dex_percent: int, forge_every: int, b: int) -> List[tuple]:
    """Batch ``b`` of ``txgen.mixed_plan`` as ``n`` (envelope, row)
    pairs on the seeded chain's sequence numbers, the forged positions
    signed with one bit of S flipped."""
    accts = txgen.keys(seed, "acct", n_accounts)
    asset = txgen.asset_credit(b"LOAD", txgen.issuer(seed).pub)
    forged = forged_index(seed, b, n, forge_every)
    out = []
    for i, (a, s, op) in enumerate(txgen.mixed_plan(
            seed, n_accounts, n, dex_percent, b)):
        if op[0] == "offer":
            x = txgen.sell_offer_op(txgen.asset_native(), asset, *op[1:])
        else:
            x = txgen.payment_op(accts[op[1]].pub, op[2])
        out.append(txgen.signed(network_id, accts[a],
                                account_base_seq() + s, [x], txgen.FEE,
                                forge=i in forged))
    return out


def expected(seed: int, n_accounts: int, n: int, dex_percent: int,
             forge_every: int, n_batches: int):
    """``ledger_reference.expected`` for this chain: after batches 0 to
    ``n_batches - 1``, every seeded account's (native balance, sequence
    number, numSubEntries, LOAD balance), the issuer's among them, and
    the book.  A forged tx is charged its fee and consumes its sequence
    number, and does nothing else."""
    pubs = [k.pub for k in txgen.keys(seed, "acct", n_accounts)]
    bal = [txgen.BALANCE] * n_accounts
    seq = [account_base_seq()] * n_accounts
    subs = [1] * n_accounts
    book: Counter = Counter()
    for b in range(n_batches):
        forged = forged_index(seed, b, n, forge_every)
        for i, (a, s, op) in enumerate(txgen.mixed_plan(
                seed, n_accounts, n, dex_percent, b)):
            bal[a] -= txgen.FEE
            seq[a] = account_base_seq() + s
            if i in forged:
                continue
            if op[0] == "pay":
                bal[a] -= op[2]
                bal[op[1]] += op[2]
            else:
                rests = ledger_reference.resting_amount(*op[1:])
                if rests:
                    subs[a] += 1
                    book[(pubs[a], rests) + op[2:]] += 1
    state = {pubs[a]: (bal[a], seq[a], subs[a], txgen.CREDIT)
             for a in range(n_accounts)}
    state[txgen.issuer(seed).pub] = issuer_state(n_accounts)
    return state, book


class Driver:
    def __init__(self, config, traffic, seed, out_dir, control):
        self.p = dict(traffic["params"])
        self.config = config
        self.seed = seed
        self.out_dir = out_dir
        self.work = os.path.join(out_dir, "work")
        self.archive_dir = os.path.join(self.work, "archive")
        self.control = control
        self.settings = dict(config["node"], HISTORY_ARCHIVES=[
            ("archive", self.archive_dir)])
        self.catchups: List[dict] = []   # every catchup of every window
        self.k = 0                       # catchups started, warm one too
        self._notes: List[str] = []

    # -- set-up ------------------------------------------------------------

    def setup(self) -> None:
        from stellar_core_tpu.catchup import CatchupConfiguration

        p = self.p
        count = self.config["range_ledgers"]
        # upstream's range, first: a program that cannot express it
        # stops here, before the archive is built
        CatchupConfiguration(0, count=count)
        shutil.rmtree(self.work, ignore_errors=True)
        os.makedirs(self.work)
        t0 = time.perf_counter()
        self._generate()
        freq = self.freq
        self.ranges = [(freq * (k + 1) - 1, count)
                       for k in range(1, p["replay_checkpoints"] + 1)]
        self._notes.append(
            f"archive: ledgers 2-{self.last} ({len(self.batches)} batches "
            f"of {p['txs_per_ledger']} txs, checkpoints every {freq}) in "
            f"{time.perf_counter() - t0:.3f}s ({self._gen_notes})")
        t0 = time.perf_counter()
        # the warm catchup stops after its first replayed ledger: by then
        # the checkpoint's dispatch has built (or loaded) the program.  A
        # warm catchup that fails is the program's fault, and the
        # window's catchups show it to the check
        warm = self._catchup(t0 + WARM_LIMIT_S, Annotator(False), ledgers=1)
        self._notes.append(
            f"warm catchup {warm['to']}/{warm['count']}: {warm['state']} "
            f"in {time.perf_counter() - t0:.3f}s, "
            f"{len(warm['ledgers'])} ledgers ({warm['crypto_backend']}): "
            + split(warm))
        if warm["crypto_backend"] != "tpu":
            raise RuntimeError("auto backends did not resolve to tpu")
        if self.control:
            self._install_control()

    def _generate(self) -> None:
        """The archive, on a reference-tier node (module docstring)."""
        from stellar_core_tpu.herder.tx_set import TxSetFrame
        from stellar_core_tpu.ledger.ledger_manager import LedgerCloseData
        from stellar_core_tpu.transactions.frame import (
            tx_frame_from_envelope,
        )

        T = _T()
        p = self.p
        n_acc = self.config["accounts"]
        # the reference tiers: host crypto, host tally, sequential apply
        # (bit-identical to the parallel and native paths replay runs,
        # and flat in cost where the planner's grows with the book)
        app = make_node(self.work, "generator", dict(
            self.settings, CRYPTO_BACKEND="cpu", SCP_TALLY_BACKEND="host",
            PARALLEL_APPLY=False))
        lm = app.ledger_manager
        nid = app.config.network_id()
        self.freq = freq = app.history_manager.checkpoint_frequency()
        self.last = freq * (p["replay_checkpoints"] + 1) - 1
        t0 = time.perf_counter()
        self.batches = [
            [(env, row[2]) for env, row in batch]
            for batch in _pool_batches(nid, self.seed, n_acc,
                                       p["txs_per_ledger"],
                                       p["dex_percent"], p["forge_every"],
                                       self.last - freq + 1)]
        t_batches = time.perf_counter() - t0

        def close(envs, upgrades=()):
            prev = lm.last_closed_header()
            tx_set = TxSetFrame(nid, lm.last_closed_hash(), [
                tx_frame_from_envelope(nid, e) for e in envs])
            sv = T.StellarValue.make(
                txSetHash=tx_set.contents_hash(),
                closeTime=prev.scpValue.closeTime + CLOSE_SECONDS,
                upgrades=list(upgrades),
                ext=T.StellarValue.fields[3][1].make(
                    T.StellarValueType.STELLAR_VALUE_BASIC))
            lm.close_ledger(LedgerCloseData(prev.ledgerSeq + 1, tx_set, sv))

        root = txgen.Key(nid)        # the genesis account's seed
        issuer = txgen.issuer(self.seed)
        accts = txgen.keys(self.seed, "acct", n_acc)
        load = txgen.asset_credit(b"LOAD", issuer.pub)
        t0 = time.perf_counter()
        close([txgen.signed(nid, root, 1, [create_account_op(
            issuer.pub, txgen.BALANCE + txgen.FEE * n_acc)])[0]],
            [T.LedgerUpgrade.encode(T.LedgerUpgrade.make(
                T.LedgerUpgradeType.LEDGER_UPGRADE_MAX_TX_SET_SIZE,
                p["txs_per_ledger"]))])
        close([txgen.signed(nid, root, 2 + j, ops, txgen.FEE * len(ops))[0]
               for j, ops in enumerate(chunks([
                   create_account_op(k.pub, txgen.BALANCE + txgen.FEE)
                   for k in accts], OPS_PER_TX))])
        close([txgen.signed(nid, k, (3 << 32) + 1, [change_trust_op(load)],
                            txgen.FEE)[0] for k in accts])
        close([txgen.signed(nid, issuer, (2 << 32) + 1 + j, ops,
                            txgen.FEE * len(ops))[0]
               for j, ops in enumerate(chunks([
                   txgen.payment_op(k.pub, txgen.CREDIT, load)
                   for k in accts], OPS_PER_TX))])
        while lm.last_closed_seq() < freq - 1:
            close([])
        t_seed = time.perf_counter() - t0
        t0 = time.perf_counter()
        for batch in self.batches:
            close([env for env, _ in batch])
        t_mixed = time.perf_counter() - t0
        lm.pipeline.drain()
        has = app.history_manager.archives[0].get_root_has()
        if has is None or has.current_ledger != self.last:
            raise RuntimeError(f"the archive ends at "
                               f"{has and has.current_ledger}, not "
                               f"{self.last}")
        self.headers = {s: bytes(d) for s, d in app.database.execute(
            "SELECT ledgerseq, data FROM ledgerheaders")}
        app.graceful_stop()
        self._gen_notes = (f"batches signed {t_batches:.3f}s, seeding "
                           f"ledgers {t_seed:.3f}s, MIXED ledgers "
                           f"{t_mixed:.3f}s")

    def _install_control(self) -> None:
        """The control: replay with verification skipped, every verdict
        of every checkpoint true."""
        from stellar_core_tpu.herder import tx_set

        tx_set.verify_signature_batch = \
            lambda triples, tracer=None: {t: True for t in triples}

    # -- the window --------------------------------------------------------

    def _catchup(self, deadline, ann, ledgers=None) -> dict:
        """One catchup of the next range on a fresh node, until it ends,
        the clock reaches ``deadline`` or it has closed ``ledgers``; its
        record."""
        from stellar_core_tpu.catchup import (
            CatchupConfiguration,
            CatchupWork,
        )

        clock = time.perf_counter
        to, count = self.ranges[self.k % len(self.ranges)]
        name = f"catchup-{self.k}"
        self.k += 1
        rec = {"name": name, "to": to, "count": count, "ledgers": [],
               "t0": clock(), "sigbatch_at": None}
        with ann.span("bench.catchup", to=to, count=count):
            app = make_node(self.work, name, self.settings)
            rec["t_node"] = clock()
            rec["crypto_backend"] = app.config.CRYPTO_BACKEND
            db = sqlite3.connect(
                f"file:{os.path.join(self.work, name, 'node.db')}?mode=ro",
                uri=True)
            lm = app.ledger_manager
            ws = app.work_scheduler
            work = CatchupWork(app, app.history_manager.archives[0],
                               CatchupConfiguration(to, count=count))
            ws.schedule(work)
            last = 0
            while not work.done and clock() < deadline and \
                    len(rec["ledgers"]) != ledgers:
                ws.crank()
                for r in app.tracer.closes():
                    if r.seq <= last:
                        continue
                    last = r.seq
                    newest = r.seq == lm.last_closed_seq()
                    if not rec["ledgers"]:
                        # the first checkpoint's batch ran before the
                        # first close, which files its span
                        rec["sigbatch_at"] = next(
                            ((sp.t0, sp.t1) for sp in r.spans
                             if sp.name == "catchup.replay.sigbatch"), None)
                    rec["ledgers"].append({
                        "seq": r.seq, "t": clock(),
                        "close_ms": r.duration_s * 1e3,
                        "phases": (dict(lm.last_close_phases) if newest
                                   else {}),
                        "hash": lm.last_closed_hash() if newest else None,
                        "durable": db.execute(
                            "SELECT 1 FROM ledgerheaders WHERE ledgerseq=?",
                            (r.seq,)).fetchone() is not None})
            rec["t_end"] = clock()
            m = app.metrics
            rec["state"] = work.state.name
            rec["sigbatch_s"] = [m.timer("catchup.replay.sigbatch").count,
                                 m.timer("catchup.replay.sigbatch")._sum]
            rec["sig_dispatches"] = m.counter(
                "catchup.replay.sig_dispatches").count
            rec["sigs_batched"] = m.counter(
                "catchup.replay.sigs_batched").count
            rec["native_declines"] = m.counter("apply.native.decline").count
            phases = [m.timer(f"catchup.phase.{ph}")
                      for ph in ("verify", "apply")]
            rec["prelude_s"] = (sum(t._sum for t in phases)
                                if all(t.count for t in phases) else None)
            db.close()
            app.graceful_stop()
        rec["t_stop"] = clock()
        return rec

    def window(self, seconds: float, mode: str) -> dict:
        """Catch up for ``seconds``; the window's record.  With ``mode``
        "profile" each catchup is also a profiler annotation."""
        ann = Annotator(mode == "profile")
        t_start = time.perf_counter()
        catchups = []
        while time.perf_counter() - t_start < seconds:
            catchups.append(self._catchup(t_start + seconds, ann))
        self.catchups += catchups
        return {"catchups": catchups, "t": (t_start, catchups[-1]["t_end"]),
                "spans": ann.spans}

    def release(self) -> None:
        pass

    # -- results -----------------------------------------------------------

    def _txs(self, seq: int) -> int:
        b = seq - self.freq
        return len(self.batches[b]) if 0 <= b < len(self.batches) else 0

    def end_to_end(self, rec: dict) -> dict:
        n = sum(self._txs(l["seq"]) for c in rec["catchups"]
                for l in c["ledgers"])
        t0, t1 = rec["t"]
        return {"applied_tx_per_s": n / (t1 - t0)}

    def layer_data(self, rec: dict) -> dict:
        """Each catchup's record with its ledgers' close times, and
        every replayed close's phases under ``closes``, where the close
        cell's phase readers find them."""
        return {"catchups": [
            {k: v for k, v in c.items() if k != "ledgers"}
            | {"close_ms": [l["close_ms"] for l in c["ledgers"]]}
            for c in rec["catchups"]],
            "closes": [{"phases": l["phases"]} for c in rec["catchups"]
                       for l in c["ledgers"]],
            "spans": rec["spans"]}

    def check(self) -> dict:
        """The one number compared: txs that failed a guarantee.  Every
        tx of a ledger fails whose stored header differs from the
        archive's, whose hash the node reported differs, or that was not
        in the database when its close returned; otherwise a tx fails
        whose stored result is missing or not the plan's, or that
        touches (as source or payee) a seeded account whose state at the
        catchup's last ledger differs from the plain reference's.  A
        wrong entry that no tx touches counts as one."""
        p = self.p
        n_acc = self.config["accounts"]
        pubs = [k.pub for k in txgen.keys(self.seed, "acct", n_acc)]
        plans: Dict[int, list] = {}
        failed = bad_headers = bad_results = bad_state = 0
        for c in self.catchups:
            if not c["ledgers"]:
                continue
            db = sqlite3.connect(
                f"file:{os.path.join(self.work, c['name'], 'node.db')}"
                "?mode=ro", uri=True)
            stored = dict(db.execute(
                "SELECT ledgerseq, data FROM ledgerheaders"))
            last = c["ledgers"][-1]["seq"]
            bad = ledger_reference.wrong_accounts(
                expected(self.seed, n_acc, p["txs_per_ledger"],
                         p["dex_percent"], p["forge_every"],
                         last - self.freq + 1),
                ledger_reference.stored(db, self.seed))
            failed += len(bad - set(pubs))
            bad_state += len(bad)
            for led in c["ledgers"]:
                seq, b = led["seq"], led["seq"] - self.freq
                want = self.headers.get(seq)
                data = stored.get(seq)
                if data is None or bytes(data) != want or \
                        led["hash"] not in (None,
                                            hashlib.sha256(want).digest()) \
                        or not led["durable"]:
                    bad_headers += 1
                    failed += self._txs(seq)
                    continue
                got = {bytes(txid): bytes(res) for txid, res in db.execute(
                    "SELECT txid, txresult FROM txhistory WHERE "
                    "ledgerseq=?", (seq,))}
                forged = forged_index(self.seed, b, p["txs_per_ledger"],
                                      p["forge_every"])
                if b not in plans:
                    plans[b] = txgen.mixed_plan(
                        self.seed, n_acc, p["txs_per_ledger"],
                        p["dex_percent"], b)
                for i, ((_, txid), (a, _, op)) in enumerate(
                        zip(self.batches[b], plans[b])):
                    res = got.get(txid)
                    wrong = res is None or result_code(res) != (
                        TX_BAD_AUTH if i in forged else 0) or \
                        int.from_bytes(res[32:40], "big") != txgen.FEE
                    bad_results += wrong
                    if wrong or pubs[a] in bad or \
                            (op[0] == "pay" and pubs[op[1]] in bad):
                        failed += 1
            db.close()
        self._failed = failed
        self._notes.append(
            "guarantees over {} catchups, {} ledgers: {} ledgers stored "
            "or reported wrong or not durable, {} tx results wrong, {} "
            "accounts whose state differs from the plain reference's"
            .format(len(self.catchups),
                    sum(len(c["ledgers"]) for c in self.catchups),
                    bad_headers, bad_results, bad_state))
        return {"txs_failed": {"value": failed, "limit": 0}}

    def counts(self, checks):
        return sum(self._txs(l["seq"]) for c in self.catchups
                   for l in c["ledgers"]), self._failed

    def notes(self, rec: dict):
        """The run's set-up notes, then each catchup of the window with
        its closes' mean phases (every replayed close in ``closes.json``
        in the run's directory)."""
        with open(os.path.join(self.out_dir, "closes.json"), "w") as f:
            json.dump([{"catchup": c["name"], "seq": l["seq"],
                        "close_ms": l["close_ms"], "phases": l["phases"]}
                       for c in self.catchups for l in c["ledgers"]], f)
        out = list(self._notes)
        for c in rec["catchups"]:
            led = c["ledgers"]
            phases: Dict[str, List[float]] = {}
            for l in led:
                for k, v in l["phases"].items():
                    if isinstance(v, float) and k != "total":
                        phases.setdefault(k, []).append(v)
            out.append(
                f"{c['name']} {c['to']}/{c['count']}: {c['state']}, "
                f"{len(led)} ledgers closed, prelude "
                f"{c['prelude_s'] and round(c['prelude_s'], 3)}s, "
                f"{c['sig_dispatches']} dispatches of "
                f"{c['sigs_batched']} sigs in {c['sigbatch_s'][1]:.3f}s, "
                f"{c['native_declines']} native apply declines, "
                f"close {sum(l['close_ms'] for l in led) / max(1, len(led)):.1f}"
                " ms a ledger (phases, mean ms: " + ", ".join(
                    f"{k} {sum(v) / len(v):.1f}"
                    for k, v in phases.items()) + ")")
        return out


def split(c: dict) -> str:
    """Where one catchup's seconds went, up to its first replayed close
    and the node's stop: node start, the prelude (the node's
    ``catchup.phase.verify`` and ``catchup.phase.apply`` timers), frames
    and work-scheduler turns up to the checkpoint's batch, the batch
    (its ``catchup.replay.sigbatch`` span), the first close, and the
    rest up to the stop."""
    if not c["ledgers"] or c["sigbatch_at"] is None:
        return "not split (no batch before a close)"
    sb0, sb1 = c["sigbatch_at"]
    first = c["ledgers"][0]
    prelude = c["prelude_s"] or 0.0
    return (f"node start {c['t_node'] - c['t0']:.3f}s, prelude "
            f"{prelude:.3f}s, frames and turns "
            f"{sb0 - c['t_node'] - prelude:.3f}s, batch {sb1 - sb0:.3f}s, "
            f"to the first close's end {first['t'] - sb1:.3f}s (the close "
            f"{first['close_ms'] / 1e3:.3f}s), after it "
            f"{c['t_stop'] - first['t']:.3f}s")


def _pool_batches(network_id, seed, n_accounts, n, dex_percent, forge_every,
                  count):
    """``replay_batch`` 0 to ``count - 1``, built on ``benchmark.pool``."""
    from benchmark import pool

    return pool.spawn_map(replay_batch, [
        (network_id, seed, n_accounts, n, dex_percent, forge_every, b)
        for b in range(count)])
