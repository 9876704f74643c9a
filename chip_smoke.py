#!/usr/bin/env python
"""Chip smoke: the validator's device path, once, on one TPU, in one process.

Phases, in order; the first failure exits non-zero and prints no result.

0. Preflight: refuse anything but a TPU backend, enable the compile cache,
   report the device, the jax/jaxlib/libtpu versions and the three native
   libraries (built from the committed sources on first use).
1. Kernels at full width: a 100,000-signature payment TxSetFrame
   (BASELINE config #2) built with LoadGenerator, plus forged rows and the
   libsodium edge vectors, verified by BOTH ed25519 kernels (Pallas and
   XLA) at the padded 131072 bucket; every verdict must equal the CPU
   reference.  Then the quorum kernels (federated_ratify, is_v_blocking,
   contract_batch) on a 64-validator tiered qset against the scalar oracle
   in scp/local_node.py.
2. The node itself: a standalone validator built as ``cmd_run`` builds it
   (config -> Application -> start()) with the backends left at "auto",
   on a virtual clock so runs are reproducible.  It must resolve to the
   device tiers, close 1000-tx mixed ledgers admitted through
   ``herder.recv_transaction`` (the /tx call) and closed by
   ``herder.manual_close`` (the /manualclose call) with the device verify
   span in every close and tensor tallies with no host fallback, and
   produce the same ledger header hashes as a CPU/host node fed the same
   traffic in the same process.

The last stdout line is ``{"ok": true, "device": {...}}``.  Compile cache:
``JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``.
"""
import json
import os
import shutil
import sys
import tempfile
import time

N_SIGS = 100_000       # BASELINE config #2: a 100k-signature TxSetFrame
FORGE_EVERY = 1000     # one flipped signature bit per this many rows
N_VALIDATORS = 64      # BASELINE config #5's validator count
PER_ORG = 4            # 16 orgs of 4: hierarchical_quorum's tiering
N_CANDIDATE_SETS = 256
NODE_CLOSES = 5
CLOSE_TXS = 1000
DEX_PCT = 30           # LoadGenerator MIXED_TXS shape, as bench.py
SEED = 21


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAIL: {msg}")


# -- phase 0 ------------------------------------------------------------------

def preflight():
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU; JAX's default backend is {backend!r}")
    from importlib.metadata import version

    from stellar_core_tpu import native
    from stellar_core_tpu.utils.device import enable_compilation_cache

    say(f"compile cache: {enable_compilation_cache()}")
    dev = jax.devices()[0]
    say(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"jax {jax.__version__} jaxlib {version('jaxlib')} "
        f"libtpu {version('libtpu')}")
    libs = {"_native.so": native.get_lib(),
            "_applykernel.so": native.get_apply_kernel(),
            "_xdrpack.so": native.get_xdrpack()}
    for name, lib in libs.items():
        say(f"native {name}: {'loaded' if lib is not None else 'MISSING'}")
    check(all(lib is not None for lib in libs.values()),
          "a native library did not build from source")
    return dev


# -- phase 1 ------------------------------------------------------------------

def signature_batch(n_sigs: int):
    """(pk, sg, mg) uint8 rows of a LoadGenerator payment TxSetFrame's
    collect_signature_batch(), every FORGE_EVERY-th signature forged,
    with the libsodium edge vectors appended; plus the edge row count."""
    import numpy as np

    from stellar_core_tpu.crypto import ed25519_ref as ref
    from stellar_core_tpu.herder.tx_set import TxSetFrame
    from stellar_core_tpu.main import Application, test_config
    from stellar_core_tpu.simulation.load_generator import LoadGenerator
    from stellar_core_tpu.utils.clock import ClockMode, VirtualClock
    from stellar_core_tpu.xdr import types as T

    t0 = time.perf_counter()
    app = Application(VirtualClock(ClockMode.VIRTUAL_TIME), test_config())
    app.start()
    lg = LoadGenerator(app)
    lg.create_accounts(min(n_sigs, 2000))
    xdr_set = T.TransactionSet.make(
        previousLedgerHash=app.ledger_manager.last_closed_hash(),
        txs=lg.generate_payments(n_sigs))
    tx_set = TxSetFrame.make_from_wire(app.config.network_id(), xdr_set)
    triples, _ = tx_set.collect_signature_batch()
    app.graceful_stop()
    check(len(triples) == n_sigs,
          f"tx set gave {len(triples)} signatures, not {n_sigs}")
    edge = ref.edge_vectors()
    rows = [(pk, sig.ljust(64, b"\x00"), msg) for pk, sig, msg in triples]
    for i in range(0, len(rows), FORGE_EVERY):
        pk, sig, msg = rows[i]
        rows[i] = (pk, sig[:40] + bytes([sig[40] ^ 0x10]) + sig[41:], msg)
    rows += [(pk, sig, msg) for pk, sig, msg, _ in edge]
    n = len(rows)
    pk, sg, mg = (np.frombuffer(b"".join(r[j] for r in rows),
                                np.uint8).reshape(n, w)
                  for j, w in ((0, 32), (1, 64), (2, 32)))
    say(f"signature batch: {n_sigs} tx-set rows "
        f"({len(range(0, n_sigs, FORGE_EVERY))} forged) + {len(edge)} edge "
        f"vectors, built in {time.perf_counter() - t0:.1f}s")
    return pk, sg, mg, len(edge)


def phase_kernels(n_sigs: int = N_SIGS) -> None:
    import numpy as np

    from stellar_core_tpu.crypto import verify_sig
    from stellar_core_tpu.crypto import ed25519_ref as ref
    from stellar_core_tpu.ops import ed25519_kernel, ed25519_pallas
    from stellar_core_tpu.utils.device import pad_signature_batch

    pk, sg, mg, n_edge = signature_batch(n_sigs)
    n = pk.shape[0]
    t0 = time.perf_counter()
    want = np.array([verify_sig(bytes(pk[i]), bytes(sg[i]), bytes(mg[i]))
                     for i in range(n)])
    edge_spec = [ref.verify(bytes(pk[i]), bytes(sg[i]), bytes(mg[i]))
                 for i in range(n - n_edge, n)]
    check(list(want[n - n_edge:]) == edge_spec,
          "CPU verify_sig disagrees with ed25519_ref on the edge vectors")
    say(f"CPU reference: {int(want.sum())} accept / {int((~want).sum())} "
        f"reject in {time.perf_counter() - t0:.1f}s")

    bucket = pad_signature_batch(n)
    idx = np.arange(bucket) % n
    args = (pk[idx], sg[idx], mg[idx])
    for name, mod in (("pallas", ed25519_pallas), ("xla", ed25519_kernel)):
        t0 = time.perf_counter()
        compiled = mod.verify_batch.lower(*args).compile()
        t1 = time.perf_counter()
        got = np.asarray(compiled(*args))[:n]
        t2 = time.perf_counter()
        bad = np.nonzero(got != want)[0]
        say(f"{name} kernel @ {bucket}: compile {t1 - t0:.1f}s, first call "
            f"{t2 - t1:.1f}s; {int(got.sum())} accept / "
            f"{int((~got).sum())} reject; {len(bad)} differ from CPU")
        check(len(bad) == 0,
              f"{name} kernel differs from the CPU reference at rows "
              f"{bad[:10].tolist()}")


def phase_quorum() -> None:
    import jax
    import numpy as np

    from stellar_core_tpu.crypto import sha256
    from stellar_core_tpu.ops import quorum as Q
    from stellar_core_tpu.scp import local_node as LN
    from stellar_core_tpu.scp import qset_vector
    from stellar_core_tpu.simulation.simulation import tiered_qset

    ids = [sha256(b"smoke-validator-%d" % i) for i in range(N_VALIDATORS)]
    spec = tiered_qset(ids, PER_ORG)
    qset = LN.make_qset(spec["threshold"], spec["validators"],
                        [LN.make_qset(s["threshold"], s["validators"])
                         for s in spec["inner_sets"]])
    plain = LN.qset_to_plain(qset)
    qsets = Q.build_qset_tensor([plain] * N_VALIDATORS, ids)
    local = Q.QSetTensor(*(x[0] for x in qsets))
    rng = np.random.default_rng(SEED)
    density = np.linspace(0.05, 1.0, N_CANDIDATE_SETS)[:, None]
    members = rng.random((N_CANDIDATE_SETS, N_VALIDATORS)) < density

    t0 = time.perf_counter()
    ratify = np.asarray(jax.jit(Q.federated_ratify)(local, qsets, members))
    vblock = np.asarray(jax.jit(Q.is_v_blocking)(local, members))
    contracted = np.asarray(jax.jit(Q.contract_batch)(qsets, members))
    say(f"quorum kernels @ {N_VALIDATORS} validators x "
        f"{N_CANDIDATE_SETS} sets: compile+run {time.perf_counter() - t0:.1f}s")

    def contract(nodes):
        # the scalar fixpoint: drop nodes whose slice the set lacks
        while True:
            kept = {n for n in nodes if LN.is_quorum_slice(qset, nodes)}
            if kept == nodes:
                return nodes
            nodes = kept

    prev = qset_vector.set_enabled(False)  # the scalar oracle, not numpy
    try:
        for row, m in enumerate(members):
            nodes = {ids[i] for i in np.nonzero(m)[0]}
            check(bool(ratify[row]) == LN.is_quorum(
                nodes, lambda _n: qset, qset),
                f"federated_ratify differs from local_node at set {row}")
            check(bool(vblock[row]) == LN.is_v_blocking(qset, nodes),
                  f"is_v_blocking differs from local_node at set {row}")
            got = {ids[i] for i in np.nonzero(contracted[row])[0]}
            check(got == contract(nodes),
                  f"contract_batch differs from local_node at set {row}")
    finally:
        qset_vector.set_enabled(prev)
    for name, v in (("federated_ratify", ratify), ("is_v_blocking", vblock)):
        say(f"{name}: {int(v.sum())} true / {int((~v).sum())} false; "
            "all equal the scalar oracle")
        check(0 < v.sum() < len(v), f"{name} gave one verdict for every set")
    say("contract_batch: all contractions equal the scalar oracle")


# -- phase 2 ------------------------------------------------------------------

def make_node(work_dir: str, name: str, **overrides):
    """A standalone validator built as ``cmd_run`` builds one: config,
    then Application, then start() — on a virtual clock."""
    from stellar_core_tpu.crypto import sha256
    from stellar_core_tpu.main.application import Application
    from stellar_core_tpu.main.command_line import load_config
    from stellar_core_tpu.utils.clock import ClockMode, VirtualClock

    cfg = load_config(None, dict(
        NODE_SEED=sha256(b"chip-smoke-node"),
        RUN_STANDALONE=True,
        MANUAL_CLOSE=True,
        UPGRADE_DESIRED_MAX_TX_SET_SIZE=CLOSE_TXS,
        TRACE_DIR=os.path.join(work_dir, f"traces-{name}"),
        BUCKET_DIR_PATH=os.path.join(work_dir, f"buckets-{name}"),
        **overrides))
    app = Application(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    return app


def phase_node(closes: int = NODE_CLOSES, close_txs: int = CLOSE_TXS) -> None:
    from stellar_core_tpu.simulation.load_generator import LoadGenerator

    work_dir = tempfile.mkdtemp(prefix="chip_smoke-")
    nodes = []
    try:
        dev = make_node(work_dir, "device")
        nodes.append(dev)
        info = dev.get_json_info()
        say(f"node /info: crypto_backend={info['crypto_backend']}, "
            f"tally backend={dev.config.SCP_TALLY_BACKEND}")
        check(info["crypto_backend"] == "tpu",
              "auto CRYPTO_BACKEND did not resolve to tpu")
        check(dev.config.SCP_TALLY_BACKEND == "tensor",
              "auto SCP_TALLY_BACKEND did not resolve to tensor")
        ref = make_node(work_dir, "cpu", CRYPTO_BACKEND="cpu",
                        SCP_TALLY_BACKEND="host")
        nodes.append(ref)

        def close_both(label):
            t0 = time.perf_counter()
            dev.herder.manual_close()
            t1 = time.perf_counter()
            ref.herder.manual_close()
            h_dev = dev.ledger_manager.last_closed_hash()
            h_ref = ref.ledger_manager.last_closed_hash()
            seq = dev.ledger_manager.last_closed_seq()
            say(f"close {seq} ({label}): device node {t1 - t0:.2f}s, "
                f"hash {h_dev.hex()[:16]} {'==' if h_dev == h_ref else '!='}"
                f" cpu node")
            check(h_dev == h_ref, f"ledger {seq} hash differs between the "
                  "device node and the CPU node")
            return seq

        close_both("maxTxSetSize upgrade")
        for app in nodes:
            check(app.ledger_manager.last_closed_header().maxTxSetSize
                  >= close_txs, "maxTxSetSize upgrade did not apply")
        gens = []
        for app in nodes:
            lg = LoadGenerator(app)
            lg.create_accounts(close_txs)
            lg.setup_dex()
            gens.append(lg)
        for _ in range(closes):
            envs = gens[0].generate_mixed(close_txs, dex_percent=DEX_PCT)
            for app in nodes:
                admitted = sum(1 for env in envs
                               if app.herder.recv_transaction(env) == 0)
                check(admitted == close_txs,
                      f"only {admitted}/{close_txs} txs admitted")
            seq = close_both(f"{close_txs} txs, {DEX_PCT}% DEX")
            for app in nodes:
                check(app.herder.tx_queue.size() == 0,
                      f"close {seq} left txs queued")
            rec = dev.tracer.get_close(seq)
            check(rec is not None and any(
                s.name == "crypto.sigbatch.dispatch" for s in rec.spans),
                f"close {seq} has no crypto.sigbatch.dispatch span")
        tallies = fallbacks = 0
        for slot in dev.herder.scp.slots.values():
            if slot.tally is not None:
                tallies += slot.tally.tensor_tallies
                fallbacks += slot.tally.host_fallbacks
        say(f"device node SCP: tensor_tallies={tallies} "
            f"host_fallbacks={fallbacks}")
        check(tallies > 0, "no SCP tally ran on the tensor path")
        check(fallbacks == 0, "SCP tallies fell back to the host")
    finally:
        for app in nodes:
            app.graceful_stop()
        shutil.rmtree(work_dir, ignore_errors=True)


def main() -> int:
    # libtpu's own logs go to chiprun_out/ (gitignored, returned by the
    # chip tool) instead of /tmp, outside the checkout; libtpu reads this
    # when the backend starts and does not create the directory itself
    if "TPU_LOG_DIR" not in os.environ:
        log_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "chiprun_out", "tpu_logs")
        os.makedirs(log_dir, exist_ok=True)
        os.environ["TPU_LOG_DIR"] = log_dir
    dev = preflight()
    phase_kernels()
    phase_quorum()
    phase_node()
    import jax

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
