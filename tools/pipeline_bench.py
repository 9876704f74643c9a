#!/usr/bin/env python
"""Pipelined-close A/B bench (ISSUE 11 acceptance): mixed and pay-heavy
1000-tx closes through the full node close path, alternating
pipeline-on and pipeline-off closes IN THE SAME SESSION so ledger-state
drift (book growth, bucket spills) hits both arms equally.  Persists
PIPELINE_BENCH_r12.json.

What the pipeline must prove (and this bench measures):

- close-phase p50 drops >= 20% with the commit/meta/tx-history/gc
  tail staged on the worker (``tail_ms_reclaimed``: close-thread ms
  the off arm pays inline; ``tail_deferred_ms``: flight-recorder span
  time ledger N's tail spent running AFTER N's close root ended,
  concurrent with the next cycle on the main thread);
- the footprint prefetch staged at nomination serves the close from
  the bucket tier: prefetch hit rate reported, close-thread SQL point
  reads must be 0 in BucketListDB mode;
- hashes are BIT-IDENTICAL pipeline-on vs pipeline-off: a separate
  parity pass runs the same deterministic workload twice (on vs off)
  and compares every per-close (ledger hash, bucket hash, meta bytes).

Env knobs: BENCH_CLOSES (per arm, default 8), BENCH_CLOSE_TXS
(default 1000), BENCH_DEX_PCT (default 30), BENCH_WORKERS (parallel
apply workers, default 2).
"""
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _note(msg):
    print(f"[pipeline-bench] {msg}", file=sys.stderr, flush=True)


def _pct(xs, q):
    if not xs:
        return None
    xs = sorted(xs)
    return round(xs[min(len(xs) - 1, int(q * len(xs)))], 2)


def _p50(xs):
    return round(statistics.median(xs), 2) if xs else None


def _mk_app(workers: int, node_dir=None):
    from stellar_core_tpu.main import Application, test_config
    from stellar_core_tpu.utils.clock import ClockMode, VirtualClock

    close_txs = int(os.environ.get("BENCH_CLOSE_TXS", "1000"))
    kw = {}
    if node_dir is not None:
        # production-shaped durability: a real SQLite file + on-disk
        # bucket store.  The tail the pipeline defers is exactly this
        # node's durable-commit work — benching it on :memory: would
        # understate the tail (and overlap I/O is the point)
        os.makedirs(os.path.join(node_dir, "buckets"), exist_ok=True)
        kw["DATABASE"] = os.path.join(node_dir, "node.db")
        kw["BUCKET_DIR_PATH_REAL"] = os.path.join(node_dir, "buckets")
    app = Application(VirtualClock(ClockMode.VIRTUAL_TIME), test_config(
        UPGRADE_DESIRED_MAX_TX_SET_SIZE=max(100, close_txs),
        DEFERRED_GC=True,
        PIPELINED_CLOSE=True,
        PIPELINED_CLOSE_EAGER_DRAIN=False,  # measure the real overlap
        PARALLEL_APPLY_WORKERS=workers,
        **kw))
    app.start()
    app.herder.manual_close()  # applies the max-tx-set-size upgrade
    return app


def _tail_overlap_from_ring(app) -> tuple:
    """Flight-recorder proof of the overlap, per ledger N with a
    committed record: (deferred_ms, next_close_overlap_ms) where
    deferred = tail-span time spent AFTER N's close root ended (ran
    concurrently with the next cycle's admission/nomination/close on
    the main thread) and next_close_overlap = the part of that which
    coincided with ledger N+1's close root specifically (nonzero only
    when the tail outlives the whole inter-close gap)."""
    recs = {rec.seq: rec for rec in app.tracer.closes()}
    deferred, next_overlap = [], []
    for seq, rec in recs.items():
        root_n = next((sp for sp in rec.spans
                       if sp.name == "ledger.close"), None)
        if root_n is None:
            continue
        tails = [sp for sp in rec.spans
                 if sp.name in ("ledger.close.commit",
                                "ledger.close.meta", "ledger.close.gc")]
        if not tails:
            continue
        deferred.append(round(sum(
            max(0.0, sp.t1 - max(sp.t0, root_n.t1))
            for sp in tails) * 1000.0, 3))
        nxt = recs.get(seq + 1)
        root_next = None if nxt is None else next(
            (sp for sp in nxt.spans if sp.name == "ledger.close"), None)
        if root_next is not None:
            next_overlap.append(round(sum(
                max(0.0, min(sp.t1, root_next.t1)
                    - max(sp.t0, root_next.t0))
                for sp in tails) * 1000.0, 3))
    return deferred, next_overlap


def _seed_and_fold(app, lg, n: int, close_txs: int) -> None:
    """Bulk-seed ``n`` accounts, then run one UNTIMED payment rotation
    over every slice so each account's state is written by a real
    close — folding it off the sql-ahead overlay into the BUCKET tier,
    where the footprint prefetch (and cold reads) can find it."""
    lg.create_accounts(n)
    for lo in range(0, n, close_txs):
        accts = lg.accounts[lo:lo + close_txs]
        envs = lg.generate_payments(len(accts), accounts=accts)
        admitted = sum(1 for env in envs
                       if app.herder.recv_transaction(env) == 0)
        assert admitted == len(accts), "seeding fold under-admitted"
        app.herder.manual_close()
    root = app.ledger_manager.root
    # only never-closed stragglers (the genesis root) may remain
    assert len(root._sql_ahead) < 4, \
        f"{len(root._sql_ahead)} seeded keys still on the sql-ahead " \
        f"overlay — the fold failed"


def lockdep_probe(n_closes: int, close_txs: int, workers: int) -> dict:
    """Per-close witness accounting for the --lockdep-smoke overhead
    gate: run ``n_closes`` pipelined pay closes on one app and report
    the lock acquisitions + guarded-field checks the witness performed
    PER CLOSE (lockdep.stats() delta across the timed loop only —
    seeding excluded) alongside the round-trip close p50.  Meaningful
    under LOCKDEP=1; with the witness disabled the counts are zero and
    the report says so."""
    import shutil
    import tempfile

    from stellar_core_tpu.simulation.load_generator import LoadGenerator
    from stellar_core_tpu.utils import lockdep

    node_dir = tempfile.mkdtemp(prefix="lockdep-probe-")
    app = _mk_app(workers, node_dir=node_dir)
    lg = LoadGenerator(app)
    lg.payment_pattern = "pairs"
    n_accounts = max(2 * close_txs, 4 * close_txs)
    _seed_and_fold(app, lg, n_accounts, close_txs)
    n_slices = max(1, n_accounts // close_txs)
    before = lockdep.stats()
    walls = []
    for i in range(n_closes):
        lo = (i % n_slices) * close_txs
        hi = ((i + 1) % n_slices) * close_txs
        envs = lg.generate_payments(
            close_txs, accounts=lg.accounts[lo:lo + close_txs],
            dest_accounts=lg.accounts[hi:hi + close_txs])
        admitted = sum(1 for env in envs
                       if app.herder.recv_transaction(env) == 0)
        assert admitted == close_txs, f"only {admitted} admitted"
        t0 = time.perf_counter()
        app.herder.manual_close()
        walls.append((time.perf_counter() - t0) * 1000.0)
    app.ledger_manager.pipeline.drain()
    after = lockdep.stats()
    app.graceful_stop()
    shutil.rmtree(node_dir, ignore_errors=True)
    return {
        "enabled": after["enabled"],
        "closes": n_closes,
        "close_txs": close_txs,
        "close_p50_ms": _p50(walls),
        "acquires_per_close": round(
            (after["acquires"] - before["acquires"]) / n_closes, 1),
        "guard_checks_per_close": round(
            (after["guard_checks"] - before["guard_checks"]) / n_closes,
            1),
        "inversions": after["inversions"],
        "guard_violations": after["guard_violations"],
    }


def bench_workload(shape: str, n_closes: int, close_txs: int,
                   dex_pct: int, workers: int) -> dict:
    import shutil
    import tempfile

    from stellar_core_tpu.simulation.load_generator import LoadGenerator

    node_dir = tempfile.mkdtemp(prefix=f"pipeline-bench-{shape}-")
    app = _mk_app(workers, node_dir=node_dir)
    lm = app.ledger_manager
    pipeline = lm.pipeline
    lg = LoadGenerator(app)
    lg.payment_pattern = "pairs"
    # an account pool MANY closes wide, seeded through real closes into
    # the bucket tier: each bench close draws a rotating slice whose
    # keys fell out of the 8k-entry root cache since their last touch,
    # so the footprint prefetch has real work (the 1M-entry production
    # shape scaled down)
    n_accounts = int(os.environ.get(
        "BENCH_ACCOUNTS", str(12 * close_txs)))
    _seed_and_fold(app, lg, n_accounts, close_txs)
    if shape == "mixed":
        lg.setup_dex(lg.accounts[:close_txs])
    n_slices = max(1, n_accounts // close_txs)
    arms = {"off": [], "on": []}
    phases = {"off": [], "on": []}
    sql_reads = {"off": 0, "on": 0}
    for i in range(2 * n_closes):
        arm = "on" if i % 2 else "off"
        if arm == "off":
            pipeline.drain()
        pipeline.enabled = (arm == "on")
        # sources from slice i, destinations from slice i+1: the
        # recipients-aren't-senders shape — admission pre-warms only
        # the sources, so the destination entries are the close's (and
        # the staged prefetch's) to load from the bucket tier
        lo = (i % n_slices) * close_txs
        hi = ((i + 1) % n_slices) * close_txs
        accts = lg.accounts[lo:lo + close_txs]
        dests = lg.accounts[hi:hi + close_txs]
        envs = (lg.generate_mixed(close_txs, dex_percent=dex_pct,
                                  accounts=accts, dest_accounts=dests)
                if shape == "mixed"
                else lg.generate_payments(close_txs, accounts=accts,
                                          dest_accounts=dests))
        admitted = sum(1 for env in envs
                       if app.herder.recv_transaction(env) == 0)
        assert admitted == close_txs, f"only {admitted} admitted"
        sql0 = lm.root.reads_from_sql
        t0 = time.perf_counter()
        app.herder.manual_close()
        arms[arm].append((time.perf_counter() - t0) * 1000.0)
        sql_reads[arm] += lm.root.reads_from_sql - sql0
        phases[arm].append(dict(lm.last_close_phases))
    pipeline.drain()
    deferred_ms, next_overlap_ms = _tail_overlap_from_ring(app)
    stats = dict(pipeline.stats)
    apply_stats = {k: v for k, v in app.parallel_apply.stats.items()
                   if not isinstance(v, list)}
    app.graceful_stop()
    shutil.rmtree(node_dir, ignore_errors=True)

    def phase_p50(arm, name):
        vals = [row.get(name, 0.0) for row in phases[arm]
                if isinstance(row.get(name, 0.0), (int, float))]
        return round(statistics.median(vals), 2) if vals else None

    off_p50, on_p50 = _p50(arms["off"]), _p50(arms["on"])
    close_only = {
        arm: _p50([row.get("total") for row in phases[arm]
                   if isinstance(row.get("total"), (int, float))])
        for arm in ("off", "on")}
    # tail ms reclaimed per close = deferred phases that no longer sit
    # on the close thread (the pipeline-off arm pays them inline)
    tail_off = sum(filter(None, (phase_p50("off", n)
                                 for n in ("commit", "meta", "gc"))))
    staged = stats["prefetch_staged"]
    row = {
        "shape": shape,
        "close_txs": close_txs,
        "closes_per_arm": n_closes,
        "workers": workers,
        "off_close_p50_ms": off_p50,
        "on_close_p50_ms": on_p50,
        "close_phase_p50_ms": {
            "off": close_only["off"], "on": close_only["on"],
            "on_vs_off_pct": (
                round((close_only["on"] - close_only["off"])
                      / close_only["off"] * 100.0, 1)
                if close_only["off"] else None)},
        "off_close_p99_ms": _pct(arms["off"], 0.99),
        "on_close_p99_ms": _pct(arms["on"], 0.99),
        "on_vs_off_pct": (round((on_p50 - off_p50) / off_p50 * 100.0, 1)
                          if off_p50 else None),
        "tail_ms_reclaimed_p50": round(tail_off, 2),
        "tail_deferred_ms": {
            "p50": _p50(deferred_ms), "max": _pct(deferred_ms, 1.0),
            "samples": len(deferred_ms)},
        "tail_overlap_next_close_ms": {
            "p50": _p50(next_overlap_ms),
            "max": _pct(next_overlap_ms, 1.0),
            "samples": len(next_overlap_ms)},
        "tail_wait_p50_ms": phase_p50("on", "tail_wait"),
        "stage_p50_ms": phase_p50("on", "stage"),
        "prefetch_phase_p50_ms": {
            "off": phase_p50("off", "prefetch"),
            "on": phase_p50("on", "prefetch")},
        "prefetch": {
            "staged": staged,
            "keys": stats["prefetch_keys"],
            "adopted": stats["prefetch_adopted"],
            "hit_rate": (round(stats["prefetch_adopted"]
                               / stats["prefetch_keys"], 4)
                         if stats["prefetch_keys"] else None)},
        "close_thread_sql_point_reads": sql_reads,
        "pipeline_stats": {k: (round(v, 4) if isinstance(v, float)
                               else v) for k, v in stats.items()},
        "batched_clusters": apply_stats.get("batched_clusters", 0),
        "native_hits": apply_stats.get("native_hits", 0),
    }
    _note(f"{shape}: round-trip off/on p50 {off_p50}/{on_p50}ms "
          f"({row['on_vs_off_pct']}%)  close-phase off/on p50 "
          f"{close_only['off']}/{close_only['on']}ms "
          f"({row['close_phase_p50_ms']['on_vs_off_pct']}%)  "
          f"tail reclaimed "
          f"{row['tail_ms_reclaimed_p50']}ms  deferred p50 "
          f"{row['tail_deferred_ms']['p50']}ms  prefetch hit "
          f"{row['prefetch']['hit_rate']}")
    return row


def parity_pass(close_txs: int, dex_pct: int, workers: int) -> dict:
    """Same deterministic workload, pipeline on (overlapping) vs off:
    every per-close (ledger hash, bucket hash, meta bytes) must match."""
    sys.path.insert(0, os.path.join(REPO, "tests"))
    from tests.test_pipelined_close import run_workload

    on, _ = run_workload(True, eager=False,
                         PARALLEL_APPLY_WORKERS=workers)
    off, _ = run_workload(False, PARALLEL_APPLY_WORKERS=workers)
    ok = len(on) == len(off) and all(
        a[0] == b[0] and a[1] == b[1] and a[2] == b[2]
        for a, b in zip(on, off))
    row = {"closes": len(on), "hashes_identical": ok,
           "meta_bytes_identical": ok}
    _note(f"parity: {len(on)} closes, identical={ok}")
    if not ok:
        raise SystemExit("pipeline on/off parity FAILED")
    return row


def main() -> None:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    n_closes = int(os.environ.get("BENCH_CLOSES", "8"))
    close_txs = int(os.environ.get("BENCH_CLOSE_TXS", "1000"))
    dex_pct = int(os.environ.get("BENCH_DEX_PCT", "30"))
    workers = int(os.environ.get("BENCH_WORKERS", "2"))

    if "--lockdep-probe" in sys.argv:
        row = lockdep_probe(max(4, n_closes), close_txs, workers)
        _note(f"lockdep probe: {row}")
        path = os.environ.get("PIPELINE_BENCH_OUT",
                              "/tmp/_lockdep_probe.json")
        with open(path, "w") as f:
            json.dump(row, f, indent=2)
        return

    rows = [bench_workload(shape, n_closes, close_txs, dex_pct, workers)
            for shape in ("pay", "mixed")]
    parity = parity_pass(close_txs, dex_pct, workers)

    out = {
        "bench": "pipelined-close",
        "rev": "r12",
        "device": "cpu-fallback",
        "workloads": rows,
        "parity": parity,
        "notes": (
            "alternating same-session A/B on a disk-backed node; 'on' "
            "arm overlaps the commit/meta/gc tail with the next "
            "cycle's admission/trigger/close (eager drain off); "
            "tail_deferred_ms = flight-recorder tail-span time past "
            "the close root's end (the overlap proof; "
            "tail_overlap_next_close_ms is nonzero only when a tail "
            "outlives the whole inter-close gap); round-trip = "
            "manual_close wall incl. SCP/nomination, close_phase = "
            "the close-only span; parity pass compares per-close "
            "header/bucket hashes AND meta bytes pipeline-on vs off"),
    }
    path = os.environ.get(
        "PIPELINE_BENCH_OUT", os.path.join(REPO, "PIPELINE_BENCH_r12.json"))
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    _note(f"persisted {path}")


if __name__ == "__main__":
    main()
