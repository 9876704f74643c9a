"""ISSUE 16: one planner pass, N GIL-free kernel calls — the batched
fee/seqnum phase, in-kernel pool quoting, and the native tail encode.

The consensus property is the same one every native crossing carries:
for ANY tx set, closes with each r16 feature engaged must produce
byte-identical ledger header hash, bucket-list hash and tx meta versus
the Python reference a host without the native code runs
(``txtest.native_unbuilt``), across worker counts (0 sequential / 2 /
4) and across PYTHONHASHSEED values (subprocess arms).  A fee batch the
kernel cannot charge (any unsupported source-account shape) must
decline the WHOLE batch — fee charging is strictly sequential, a
repeat source has to see the prior tx's post-image — and still match
bytes.
"""
import os
import subprocess
import sys

from stellar_core_tpu.main import Application, test_config
from stellar_core_tpu.main.http_server import CommandHandler
from stellar_core_tpu.simulation.load_generator import LoadGenerator
from stellar_core_tpu.utils.clock import ClockMode, VirtualClock
from stellar_core_tpu.xdr import types as T

from .test_parallel_apply import (
    _assert_identical, _close_and_fingerprint, _run_workload,
)
from .txtest import native_unbuilt


def _fee_metrics(app):
    return {n: m.count for n, m in app.metrics._metrics.items()
            if n.startswith(("apply.native.fee", "apply.native.tail"))}


def _capture(box):
    def hook(app):
        box["app"] = app
    return hook


# -- fee phase in-kernel -----------------------------------------------------

def test_fee_batch_on_off_bit_identical_across_worker_counts():
    """Mixed pay/DEX workload, batched fee kernel vs the per-tx
    reference loop, at workers 0/2/4 — identical fingerprints, and the
    fee batch actually engages (hit > 0, no declines on the clean
    workload)."""
    base_box = {}
    with native_unbuilt():
        base, _ = _run_workload(0, app_hook=_capture(base_box))
    assert _fee_metrics(base_box["app"]).get("apply.native.fee.hit", 0) == 0
    for workers in (0, 2, 4):
        box = {}
        fps, _ = _run_workload(workers, app_hook=_capture(box))
        _assert_identical(base, fps, f"fee batch workers={workers}")
        mets = _fee_metrics(box["app"])
        assert mets.get("apply.native.fee.hit", 0) > 0, \
            f"fee kernel never engaged at workers={workers}: {mets}"
        assert mets.get("apply.native.fee.decline", 0) == 0, mets


def test_fee_batch_repeat_sources_see_running_balance():
    """80 txs per close over 40 accounts guarantees repeat fee sources:
    each charge must see the PRIOR charge's post-image (running balance,
    bumped seqnum, accumulated feePool) — the reason the batch is
    all-or-nothing.  A different seed than the worker-count sweep keeps
    the coverage independent."""
    with native_unbuilt():
        base, _ = _run_workload(0, seed=23, n_closes=3)
    box = {}
    fps, _ = _run_workload(0, seed=23, n_closes=3, app_hook=_capture(box))
    _assert_identical(base, fps, "repeat-source fee batch")
    mets = _fee_metrics(box["app"])
    assert mets.get("apply.native.fee.hit", 0) > 0, mets


def test_unsupported_account_declines_whole_fee_batch_and_matches():
    """Fee charging is strictly sequential, so ONE unsupported source
    account (extra signer) must push the whole batch to the reference
    loop — bytes identical, and the decline taxonomy names the
    account-shape guard."""
    from .test_native_apply import _extra_signer_workload

    with native_unbuilt():
        base, _ = _extra_signer_workload(0)
    box = {}
    fps, _ = _extra_signer_workload(2, app_hook=_capture(box))
    _assert_identical(base, fps, "fee batch whole-batch decline")
    mets = _fee_metrics(box["app"])
    assert mets.get("apply.native.fee.decline", 0) > 0, mets
    assert mets.get(
        "apply.native.fee.decline.unsupported_account_shape", 0) > 0, \
        mets


# -- pool quoting in-kernel --------------------------------------------------

def _pool_workload(workers, n_closes=2, txs=30, app_hook=None):
    """payment_pattern="pool": every tx is a path payment whose hops
    cross LIVE constant-product pools (no maker books — the empty book
    loses arbitration, so the pool is the venue)."""
    app = Application(VirtualClock(ClockMode.VIRTUAL_TIME), test_config(
        TESTING_UPGRADE_MAX_TX_SET_SIZE=300,
        PARALLEL_APPLY_WORKERS=workers))
    app.start()
    if app_hook is not None:
        app_hook(app)
    lg = LoadGenerator(app)
    lg.create_accounts(12)
    lg.setup_pool(hops=2)
    fps = []
    for _ in range(n_closes):
        envs = lg.generate_payments(txs)
        assert sum(1 for e in envs
                   if app.herder.recv_transaction(e) == 0) == len(envs)
        _close_and_fingerprint(app, fps)
    stats = dict(app.parallel_apply.stats)
    pool_ids = list(lg.pool_ids)
    app.graceful_stop()
    return fps, stats, pool_ids


def test_pool_workload_native_across_worker_counts():
    """The r16 coverage-cliff fix: a live pool on a hop no longer
    declines the cluster.  Kernel quotes it at workers 2/4 and matches
    the Python reference, as does the sequential close (workers 0) with
    the fee kernel; the native hit rate stays clean (no declines)."""
    with native_unbuilt():
        base, base_stats, _ = _pool_workload(0)
    assert base_stats["native_hits"] == 0
    for workers in (0, 2, 4):
        box = {}
        fps, stats, _ = _pool_workload(workers, app_hook=_capture(box))
        _assert_identical(base, fps, f"pool workers={workers}")
        mets = _fee_metrics(box["app"])
        assert mets.get("apply.native.fee.hit", 0) > 0, (workers, mets)
        if workers:
            assert stats["native_hits"] > 0, (workers, stats)
        assert stats["native_declines"] == 0, (workers, stats)


def test_pool_reserves_move_and_pool_atom_lands_in_meta():
    """The pool crossing is visible state: reserves move off the seeded
    1:1 point and the tx meta carries CLAIM_ATOM_TYPE_LIQUIDITY_POOL
    atoms (union disc 2 followed by the poolID) — asserted on the
    native arm, so it pins real kernel pool crossings, not book
    fallbacks."""
    from stellar_core_tpu.ledger.ledger_txn import LedgerTxn, key_bytes
    from stellar_core_tpu.transactions import liquidity_pool as LP

    box = {}
    fps, stats, pool_ids = _pool_workload(2, app_hook=_capture(box))
    assert stats["native_hits"] > 0, stats
    app = box["app"]
    moved = 0
    with LedgerTxn(app.ledger_manager.root) as ltx:
        for pid in pool_ids:
            e = ltx.get(key_bytes(LP.pool_key(pid)))
            assert e is not None, "seeded pool vanished"
            cp = e.data.value.body.value
            if cp.reserveA != cp.reserveB:
                moved += 1
        ltx.rollback()
    assert moved > 0, "no pool reserves moved off the seed point"
    meta_b = b"".join(fp[2] for fp in fps)
    assert any(b"\x00\x00\x00\x02" + pid in meta_b
               for pid in pool_ids), \
        "no CLAIM_ATOM_TYPE_LIQUIDITY_POOL atom in the close meta"


# -- native tail encode ------------------------------------------------------

def test_tail_encode_on_off_bit_identical():
    """Sequential close path (workers=0, Python apply — the arm where
    ``encoded_rows is None`` and the commit tail still encodes per-row):
    one batched ``pack_many`` crossing vs the per-row Python loop,
    identical bytes, and the batch actually engages."""
    base_box = {}
    with native_unbuilt():
        base, _ = _run_workload(0, app_hook=_capture(base_box))
    assert _fee_metrics(base_box["app"]).get(
        "apply.native.tail_encode.hit", 0) == 0
    box = {}
    fps, _ = _run_workload(0, app_hook=_capture(box))
    _assert_identical(base, fps, "native tail encode")
    mets = _fee_metrics(box["app"])
    assert mets.get("apply.native.tail_encode.hit", 0) > 0, mets


def test_all_three_kill_switches_off_matches_all_on():
    """Belt and braces: a host without the native code (no apply
    kernel, fee batch, pool quoting or tail encode) vs everything on at
    workers=4, same pool workload, same bytes."""
    with native_unbuilt():
        base, _, _ = _pool_workload(0)
    fps, stats, _ = _pool_workload(4)
    _assert_identical(base, fps, "all-on vs all-off")
    assert stats["native_hits"] > 0, stats


def test_generateload_mode_pool_admin_endpoint():
    """``generateload?mode=pool`` seeds the pools on first call (no
    staged closes) and every submitted tx is admitted; closing the
    ledger drives the pool hops through the native kernel."""
    app = Application(VirtualClock(ClockMode.VIRTUAL_TIME), test_config(
        TESTING_UPGRADE_MAX_TX_SET_SIZE=300,
        PARALLEL_APPLY_WORKERS=2))
    app.start()
    handler = CommandHandler(app)
    code, body = handler.handle("generateload",
                                {"mode": "create", "accounts": "12"})
    assert code == 200, body
    app.herder.manual_close()
    code, body = handler.handle("generateload",
                                {"mode": "pool", "txs": "25"})
    assert code == 200, body
    assert body["status_counts"] == {0: 25}, body
    app.herder.manual_close()
    stats = dict(app.parallel_apply.stats)
    assert stats["native_hits"] > 0, stats
    assert stats["native_declines"] == 0, stats
    app.graceful_stop()


# -- metrics boot presence ---------------------------------------------------

def test_fee_counters_present_from_boot():
    """The /metrics scrape must carry the r16 counters before any
    traffic — JSON and Prometheus both — so dashboards and alerts can
    key on them from node boot."""
    app = Application(VirtualClock(ClockMode.VIRTUAL_TIME),
                      test_config())
    app.start()
    handler = CommandHandler(app)
    code, body = handler.handle("metrics", {})
    assert code == 200
    snap = body["metrics"]
    for name in ("apply.native.fee.hit", "apply.native.fee.decline",
                 "apply.native.tail_encode.hit"):
        assert name in snap, sorted(k for k in snap
                                    if k.startswith("apply."))
    code, raw = handler.handle("metrics", {"format": "prometheus"})
    assert code == 200
    text = raw.data.decode()
    assert "apply_native_fee_hit" in text
    assert "apply_native_fee_decline" in text
    assert "apply_native_tail_encode_hit" in text
    app.graceful_stop()


# -- hashseed invariance (subprocess arms) -----------------------------------

_HASHSEED_WORKER = """
import hashlib
import sys

sys.path.insert(0, {repo!r})
from tests.test_native_fee import _pool_workload

box = {{}}
fps, stats, _ = _pool_workload({workers}, n_closes=2, txs=20,
                               app_hook=lambda app: box.update(app=app))
fee_hits = box["app"].metrics.counter("apply.native.fee.hit").count
assert fee_hits > 0, fee_hits
assert stats["native_hits"] > 0 or {workers} == 0, stats
for lh, bh, meta in fps:
    print(lh.hex(), bh.hex(), hashlib.sha256(meta).hexdigest())
"""


def test_pool_and_fee_closes_bit_identical_under_hashseed():
    """The full r16 stack (fee batch + pool quote + tail encode, all
    default-on) closes bit-identically under different PYTHONHASHSEED
    values, at workers 0/2/4 — the subprocess arm the acceptance
    criteria pin (tests, not just bench)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for workers in (0, 2, 4):
        outputs = []
        for seed in ("0", "4242"):
            env = dict(os.environ)
            env["PYTHONHASHSEED"] = seed
            env["JAX_PLATFORMS"] = "cpu"
            proc = subprocess.run(
                [sys.executable, "-c", _HASHSEED_WORKER.format(
                    repo=repo, workers=workers)],
                capture_output=True, text=True, cwd=repo, env=env,
                timeout=600)
            assert proc.returncode == 0, proc.stderr[-4000:]
            lines = proc.stdout.strip().splitlines()
            assert len(lines) == 2, proc.stdout
            outputs.append(lines)
        a, b = outputs
        for i, (la, lb) in enumerate(zip(a, b)):
            assert la == lb, (
                f"workers={workers} close {i} diverged across "
                f"PYTHONHASHSEED:\n  seed 0   : {la}\n"
                f"  seed 4242: {lb}")
