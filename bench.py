#!/usr/bin/env python
"""Headline benchmark: the BASELINE north-star configs, on the real herder
path.

Config #2 — tx-signature verifies/sec on a large TxSetFrame: a
LoadGenerator-built payment set flows through
TxSetFrame.collect_signature_batch -> the batched device kernel (the
--crypto-backend=tpu seam the whole project exists for), against the
sequential CPU path (OpenSSL via `cryptography`, the same architecture as
the reference's PubKeyUtils::verifySig, ref src/crypto/SecretKey.cpp:428).
Config #1-adjacent — ledger-close p50: closes of 1000-tx ledgers through
the standalone node's full closeLedger path.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...},
with the device it ran on.  Runs in one process and needs a TPU: with
none, it exits non-zero and prints no rate.  The compile cache is
``JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``.

Env knobs: BENCH_N (signature batch, default 100000), BENCH_KERNEL
("pallas"|"xla", default pallas), BENCH_CLOSES (p50 sample closes,
default 24), BENCH_CLOSE_TXS (txs per close, default 1000).
"""
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))


def _note(msg):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def main() -> int:
    n_sigs = int(os.environ.get("BENCH_N", "100000"))
    n_closes = int(os.environ.get("BENCH_CLOSES", "24"))
    close_txs = int(os.environ.get("BENCH_CLOSE_TXS", "1000"))
    kernel = os.environ.get("BENCH_KERNEL", "pallas")

    import jax

    if jax.default_backend() != "tpu":
        print(f"bench: no TPU (jax default backend is "
              f"{jax.default_backend()!r}); nothing to measure",
              file=sys.stderr)
        return 1
    from stellar_core_tpu.utils.device import (
        enable_compilation_cache, pad_signature_batch,
    )

    _note(f"jax compilation cache at {enable_compilation_cache()}")
    dev = jax.devices()[0]
    if kernel == "pallas":
        from stellar_core_tpu.ops.ed25519_pallas import verify_batch
    elif kernel == "xla":
        from stellar_core_tpu.ops.ed25519_kernel import verify_batch
    else:
        raise SystemExit(f"BENCH_KERNEL must be pallas or xla, not {kernel!r}")

    import numpy as np

    from stellar_core_tpu.crypto import ed25519 as ed
    from stellar_core_tpu.main import Application, test_config
    from stellar_core_tpu.simulation.load_generator import LoadGenerator
    from stellar_core_tpu.utils.clock import ClockMode, VirtualClock

    # a close of close_txs transactions needs the ledger's maxTxSetSize
    # raised (sets above it are invalid) — done through the real upgrade
    # path on the first close, exactly like the reference's load tests
    app = Application(VirtualClock(ClockMode.VIRTUAL_TIME), test_config(
        UPGRADE_DESIRED_MAX_TX_SET_SIZE=max(100, close_txs),
        CRYPTO_BACKEND="cpu",
        DEFERRED_GC=True))  # the production close-latency GC policy
    app.start()
    app.herder.manual_close()  # applies the max-tx-set-size upgrade
    assert app.ledger_manager.last_closed_header().maxTxSetSize >= close_txs
    lg = LoadGenerator(app)
    lg.create_accounts(min(n_sigs, 2000))

    # --- build the TxSetFrame (LoadGenerator PAY mode) ---
    from stellar_core_tpu.herder.tx_set import TxSetFrame
    from stellar_core_tpu.xdr import types as T

    _note(f"building {n_sigs} payment envelopes")
    envs = lg.generate_payments(n_sigs)
    xdr_set = T.TransactionSet.make(
        previousLedgerHash=app.ledger_manager.last_closed_hash(), txs=envs)
    tx_set = TxSetFrame.make_from_wire(app.config.network_id(), xdr_set)
    _note("collecting signature batch")
    triples, _ = tx_set.collect_signature_batch()
    n = len(triples)
    pk = np.frombuffer(b"".join(t[0] for t in triples),
                       np.uint8).reshape(n, 32)
    sg = np.frombuffer(b"".join(t[1].ljust(64, b"\x00") for t in triples),
                       np.uint8).reshape(n, 64)
    mg = np.frombuffer(b"".join(t[2] for t in triples),
                       np.uint8).reshape(n, 32)

    # --- CPU baseline: sequential verifies, reference architecture ---
    n_base = min(2000, n)
    t0 = time.perf_counter()
    for i in range(n_base):
        assert ed.raw_verify(bytes(pk[i]), bytes(sg[i]), bytes(mg[i]))
    cpu_rate = n_base / (time.perf_counter() - t0)
    _note(f"cpu baseline: {cpu_rate:.0f}/s")

    # --- ledger-close p50 through the full node close path ---
    # fresh LoadGenerator: the signature batch above advanced the first
    # generator's sequence tracker without applying anything, so its next
    # envelopes would be rejected as sequence gaps
    lg2 = LoadGenerator(app)
    lg2.create_accounts(max(close_txs, 1), prefix=b"close-bench")
    # MIXED shape: payments + DEX offers (close numbers must not be
    # payments-only; ref LoadGenMode::MIXED_TXS)
    lg2.setup_dex()
    dex_pct = int(os.environ.get("BENCH_DEX_PCT", "30"))

    def run_closes(shape):
        times = []
        phase_rows = []
        for _ in range(n_closes):
            if shape == "mixed":
                envs = lg2.generate_mixed(close_txs, dex_percent=dex_pct)
            else:
                envs = lg2.generate_payments(close_txs)
            admitted = sum(1 for env in envs
                           if app.herder.recv_transaction(env) == 0)
            assert admitted == close_txs, \
                f"only {admitted}/{close_txs} txs admitted"
            t0 = time.perf_counter()
            app.herder.manual_close()
            times.append((time.perf_counter() - t0) * 1000)
            phase_rows.append(dict(app.ledger_manager.last_close_phases))
            # the upgraded maxTxSetSize must have let the WHOLE batch
            # close — a trimmed set would silently measure less
            assert app.herder.tx_queue.size() == 0, "close left txs"
        return times, phase_rows

    pay_times, _pay_phases = run_closes("pay")
    close_times, close_phases = run_closes("mixed")
    # tracing-disabled A/B in the same session: the flight recorder's
    # span instrumentation must cost <1% of close p50 when recording is
    # off (the always-on cost is two perf_counter reads per span)
    app.tracer.enabled = False
    disabled_times, _ = run_closes("mixed")
    app.tracer.enabled = True
    pay_p50 = statistics.median(pay_times) if pay_times else None
    close_p50 = statistics.median(close_times) if close_times else None
    disabled_p50 = (statistics.median(disabled_times)
                    if disabled_times else None)
    import math

    close_p99 = (sorted(close_times)[
        max(0, math.ceil(len(close_times) * 0.99) - 1)]
        if close_times else None)
    close_max = max(close_times) if close_times else None
    if close_p50 is not None:
        _note(f"close p50: {close_p50:.1f} ms  p99: {close_p99:.1f} ms  "
              f"max: {close_max:.1f} ms at {close_txs} txs over "
              f"{len(close_times)} closes (crossing level-0/1 spill "
              "boundaries; FutureBucket staging + deferred GC keep "
              "p99 near p50)")

    # --- flight-recorder evidence: per-op-type apply attribution + the
    # tracing-overhead measurement, persisted to BENCH_TRACE_r08.json ---
    op_keys = sorted({k for row in close_phases
                      for k in (row.get("apply_ops") or {})})
    apply_op_type_ms = {
        k: round(statistics.median(
            (row.get("apply_ops") or {}).get(k, 0.0)
            for row in close_phases), 3)
        for k in op_keys}
    _note(f"apply_op_type_ms (median/close): {apply_op_type_ms}")
    # disabled-span microcost: a Span always takes two perf_counter
    # reads; recording is skipped when disabled
    from stellar_core_tpu.utils.tracing import Tracer

    _dis = Tracer(enabled=False)
    n_probe = 200_000
    t0 = time.perf_counter()
    for _ in range(n_probe):
        with _dis.span("bench.overhead.probe"):
            pass
    disabled_span_ns = (time.perf_counter() - t0) / n_probe * 1e9
    last_rec = app.tracer.get_close()
    spans_per_close = len(last_rec.spans) if last_rec is not None else 0
    disabled_overhead_pct = (
        round(disabled_span_ns * 1e-6 * spans_per_close
              / close_p50 * 100.0, 4)
        if close_p50 else None)
    trace_line = {
        "metric": "ledger_close_flight_recorder",
        "close_txs": close_txs,
        "close_shape": f"mixed({dex_pct}% dex)",
        "close_samples": len(close_times),
        "apply_op_type_ms": apply_op_type_ms,
        "close_p50_ms_tracing_enabled": (round(close_p50, 2)
                                         if close_p50 else None),
        "close_p50_ms_tracing_disabled": (round(disabled_p50, 2)
                                          if disabled_p50 else None),
        "spans_per_close": spans_per_close,
        "disabled_span_cost_ns": round(disabled_span_ns, 1),
        "tracing_disabled_overhead_pct_of_close_p50":
            disabled_overhead_pct,
        "close_phase_ms_median": {
            ph: round(statistics.median(
                row.get(ph, 0.0) for row in close_phases), 3)
            for ph in ("prefetch", "verify", "fee", "apply", "upgrades",
                       "hash", "bucket", "spill_wait", "bucket_hash",
                       "commit", "meta", "gc", "total")
        } if close_phases else None,
    }
    with open(os.path.join(REPO, "BENCH_TRACE_r08.json"), "w") as f:
        json.dump(trace_line, f, indent=1)
    _note(f"tracing overhead: {disabled_span_ns:.0f}ns/span disabled x "
          f"{spans_per_close} spans/close = "
          f"{disabled_overhead_pct}% of close p50 "
          f"(persisted to BENCH_TRACE_r08.json)")

    # --- device stage: pad to the fixed batch bucket, compile + warm,
    # then time steady-state calls ---
    n_dev = pad_signature_batch(n)
    idx = np.arange(n_dev) % n
    dpk, dsg, dmg = pk[idx], sg[idx], mg[idx]
    t0 = time.perf_counter()
    ok = np.asarray(verify_batch(dpk, dsg, dmg))  # compile + warm
    compile_s = time.perf_counter() - t0
    assert ok.all(), f"kernel rejected {int((~ok).sum())} valid signatures"
    reps = 3
    t0 = time.perf_counter()
    for _ in range(reps):
        ok = np.asarray(verify_batch(dpk, dsg, dmg))
    tpu_rate = n_dev * reps / (time.perf_counter() - t0)
    _note(f"{kernel} kernel: {tpu_rate:.0f}/s at batch {n_dev} "
          f"(compile+warm {compile_s:.1f}s)")

    line = {
        "metric": "ed25519_verifies_per_sec_txset",
        "value": round(tpu_rate, 1),
        "unit": "verifies/s",
        "vs_baseline": round(tpu_rate / cpu_rate, 2),
        "cpu_verifies_per_sec": round(cpu_rate, 1),
        "n_signatures": n,
        "batch": n_dev,
        "kernel": kernel,
        "compile_s": round(compile_s, 1),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "ledger_close_p50_ms": (round(close_p50, 1)
                                if close_p50 is not None else None),
        "ledger_close_p99_ms": (round(close_p99, 1)
                                if close_p99 is not None else None),
        "ledger_close_max_ms": (round(close_max, 1)
                                if close_max is not None else None),
        "close_samples": len(close_times),
        "close_txs": close_txs,
        "close_shape": f"mixed({dex_pct}% dex)",
        "ledger_close_p50_ms_payments": (round(pay_p50, 1)
                                         if pay_p50 is not None else None),
        # flight recorder: per-op-type apply attribution (median ms per
        # mixed close) — full detail in BENCH_TRACE_r08.json
        "apply_op_type_ms": apply_op_type_ms,
        # per-phase close breakdown (median ms across the mixed closes):
        # verify/fee/apply/bucket(spill_wait,bucket_hash)/hash/commit/gc —
        # the async-merge-pipeline evidence future BENCH_r*.json track
        "close_phase_ms": {
            ph: round(statistics.median(
                row.get(ph, 0.0) for row in close_phases), 2)
            for ph in ("verify", "fee", "apply", "bucket", "spill_wait",
                       "bucket_hash", "hash", "commit", "gc", "total")
        } if close_phases else None,
        "bucket_merge_stats": {
            k: (round(v, 4) if isinstance(v, float) else v)
            for k, v in
            app.bucket_manager.bucket_list.stats.items()},
    }
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
