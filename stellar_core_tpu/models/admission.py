"""The flagship device pipeline: transaction-admission step.

This is the TPU analog of the reference's tx-admission + SCP-tally hot paths
(SURVEY.md §3.2/§3.3): one XLA program that

  1. verifies a batch of ed25519 signatures (the ``PubKeyUtils::verifySig``
     seam, ref src/crypto/SecretKey.cpp:428) — data-parallel over the batch;
  2. runs federated-voting tallies for a batch of candidate statements over
     the validator universe (the ``LocalNode::isQuorum``/``isVBlocking``
     seam, ref src/scp/LocalNode.h:58-78) — boolean matrix reductions.

``admission_step`` is the driver's ``entry()``; ``dryrun_sharded`` jits the
same step over an n-device ``jax.sharding.Mesh`` with data-parallel sharding
of the signature batch and replicated quorum tensors (DP over sigs is where
all the FLOPs are; the tally matrices are tiny and ride along replicated —
the multi-chip layout SURVEY.md §2.17 P5/P6 prescribes).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import quorum as Q
from ..ops.ed25519_kernel import _verify_impl


class AdmissionBatch(NamedTuple):
    pubkeys: jnp.ndarray   # (S, 32) uint8
    sigs: jnp.ndarray      # (S, 64) uint8
    msgs: jnp.ndarray      # (S, 32) uint8
    qset: Q.QSetTensor     # batched per-node, leading axis N
    local_qset: Q.QSetTensor  # unbatched (the local node's qset)
    voted: jnp.ndarray     # (C, N) bool
    accepted: jnp.ndarray  # (C, N) bool


def admission_step(batch: AdmissionBatch):
    """One fused admission step: sig verify + federated-accept tally.

    Returns (sig_ok (S,) bool, accept (C,) bool, ratify (C,) bool).
    """
    sig_ok = _verify_impl(batch.pubkeys, batch.sigs, batch.msgs)
    ratify = Q.federated_ratify(
        batch.local_qset, batch.qset, batch.voted | batch.accepted
    )
    accept = Q.federated_accept(
        batch.local_qset, batch.qset, batch.voted, batch.accepted,
        ratified=ratify,
    )
    return sig_ok, accept, ratify


def example_batch(n_sigs: int = 8, n_nodes: int = 4) -> tuple:
    """Build a real example batch (valid signatures, 3-of-4 style quorums)."""
    from ..crypto import SecretKey, sha256

    pubs, sigs, msgs = [], [], []
    for i in range(n_sigs):
        sk = SecretKey(sha256(b"entry%d" % i))
        m = sha256(b"msg%d" % i)
        pubs.append(sk.public_key().raw)
        sigs.append(sk.sign(m))
        msgs.append(m)
    pk = np.frombuffer(b"".join(pubs), np.uint8).reshape(n_sigs, 32)
    sg = np.frombuffer(b"".join(sigs), np.uint8).reshape(n_sigs, 64)
    mg = np.frombuffer(b"".join(msgs), np.uint8).reshape(n_sigs, 32)

    nodes = list(range(n_nodes))
    thr = n_nodes - n_nodes // 3  # 2f+1 of 3f+1
    qsets = [(thr, nodes, []) for _ in nodes]
    qt = Q.build_qset_tensor(qsets, nodes)
    local = Q.QSetTensor(
        qt.top_mem[0], qt.top_thr[0], qt.inner_mem[0], qt.inner_thr[0]
    )
    c = 4
    rng = np.random.default_rng(3)
    voted = jnp.asarray(rng.random((c, n_nodes)) < 0.8)
    accepted = jnp.asarray(rng.random((c, n_nodes)) < 0.5)
    batch = AdmissionBatch(
        jnp.asarray(pk), jnp.asarray(sg), jnp.asarray(mg),
        qt, local, voted, accepted,
    )
    return (batch,)


def multi_validator_tally(qt: Q.QSetTensor, voted, accepted):
    """Ballot tallies for N simulated validators at once (BASELINE config
    #5): validator v evaluates federated accept/ratify against ITS OWN
    quorum set over the shared statement matrix — a vmap over the
    validator axis that pjit shards across the mesh, so each device
    carries a slice of the validator universe and the boolean reductions
    run as one batched program (ref LocalNode::isQuorum
    src/scp/LocalNode.h:58-78 evaluated per-validator)."""
    def one_validator(i):
        local = Q.QSetTensor(qt.top_mem[i], qt.top_thr[i],
                             qt.inner_mem[i], qt.inner_thr[i])
        ratify = Q.federated_ratify(local, qt, voted | accepted)
        accept = Q.federated_accept(local, qt, voted, accepted,
                                    ratified=ratify)
        return accept, ratify

    n = qt.top_mem.shape[0]
    return jax.vmap(one_validator)(jnp.arange(n))


def bench_sharded(n_devices: int, n_sigs: int = 100_000,
                  n_validators: int = 64, n_candidates: int = 64,
                  reps: int = 1) -> dict:
    """Bench-shaped multi-chip admission: shard a ``n_sigs`` verify batch
    (DP) and a ``n_validators`` ballot tally (validator-parallel) over an
    n-device mesh; return timings + per-device throughput.

    On the virtual CPU mesh all "devices" share one host's cores, so the
    absolute rate is the host-CPU XLA rate (orders below both libsodium
    and the TPU MXU path) — the artifact this produces is evidence of the
    sharded PROGRAM at bench shapes, with honest labeling, not a TPU
    throughput claim."""
    import time

    from ..parallel import data_parallel_mesh, dp as dp_of, replicated

    mesh = data_parallel_mesh(n_devices)
    dp = dp_of(mesh)
    rep = replicated(mesh)

    # -- signature workload ------------------------------------------------
    from ..crypto import SecretKey, sha256

    keys = [SecretKey(sha256(b"mcb%d" % i)) for i in range(64)]
    rng = np.random.default_rng(7)
    mg = rng.integers(0, 256, (n_sigs, 32), dtype=np.uint8)
    pk = np.empty((n_sigs, 32), np.uint8)
    sg = np.empty((n_sigs, 64), np.uint8)
    for i in range(n_sigs):
        k = keys[i % 64]
        pk[i] = np.frombuffer(k.public_key().raw, np.uint8)
        sg[i] = np.frombuffer(k.sign(bytes(mg[i])), np.uint8)
    pk, sg, mg = (jax.device_put(jnp.asarray(x), dp)
                  for x in (pk, sg, mg))

    verify = jax.jit(_verify_impl, out_shardings=dp)
    t0 = time.perf_counter()
    ok = np.asarray(verify(pk, sg, mg))
    compile_s = time.perf_counter() - t0
    assert ok.all(), "sharded verify rejected valid signatures"
    t0 = time.perf_counter()
    for _ in range(reps):
        ok = verify(pk, sg, mg)
    ok.block_until_ready()
    verify_dt = (time.perf_counter() - t0) / reps

    # -- multi-validator ballot tally, validator axis sharded -------------
    nodes = list(range(n_validators))
    thr = n_validators - n_validators // 3
    qt = Q.build_qset_tensor([(thr, nodes, []) for _ in nodes], nodes)
    rng = np.random.default_rng(11)
    voted = jnp.asarray(rng.random((n_candidates, n_validators)) < 0.8)
    accepted = jnp.asarray(rng.random((n_candidates, n_validators)) < 0.5)
    qt_s = Q.QSetTensor(*(jax.device_put(t, dp) for t in qt))
    voted, accepted = (jax.device_put(x, rep) for x in (voted, accepted))
    tally = jax.jit(multi_validator_tally, out_shardings=(dp, dp))
    t0 = time.perf_counter()
    acc, rat = tally(qt_s, voted, accepted)
    acc.block_until_ready()
    tally_compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(max(reps, 10)):
        acc, rat = tally(qt_s, voted, accepted)
    acc.block_until_ready()
    tally_dt = (time.perf_counter() - t0) / max(reps, 10)
    assert acc.shape == (n_validators, n_candidates)

    dev0 = jax.devices()[0]
    return {
        "n_devices": n_devices,
        "device_kind": getattr(dev0, "device_kind", dev0.platform),
        "platform": dev0.platform,
        "n_signatures": n_sigs,
        "verify_compile_s": round(compile_s, 1),
        "verify_step_s": round(verify_dt, 3),
        "verify_sigs_per_s": round(n_sigs / verify_dt, 1),
        "verify_sigs_per_s_per_device": round(
            n_sigs / verify_dt / n_devices, 1),
        "n_validators": n_validators,
        "n_candidates": n_candidates,
        "tally_compile_s": round(tally_compile_s, 2),
        "tally_step_s": round(tally_dt, 5),
        "validator_tallies_per_s": round(
            n_validators * n_candidates / tally_dt, 1),
    }


def dryrun_sharded(n_devices: int) -> None:
    """jit the full admission step over an n-device mesh and run one step.

    Signature batch is sharded over the ``data`` axis (DP); quorum tensors
    replicated.  Executes on tiny shapes to validate the multi-chip layout
    compiles and runs (driver calls this with a virtual CPU mesh).
    """
    from ..parallel import data_parallel_mesh, dp as dp_of, replicated

    mesh = data_parallel_mesh(n_devices)

    (batch,) = example_batch(n_sigs=2 * n_devices, n_nodes=4)
    dp = dp_of(mesh)
    rep = replicated(mesh)

    def put(x, sh):
        return jax.device_put(x, sh)

    sharded = AdmissionBatch(
        put(batch.pubkeys, dp),
        put(batch.sigs, dp),
        put(batch.msgs, dp),
        Q.QSetTensor(*(put(t, rep) for t in batch.qset)),
        Q.QSetTensor(*(put(t, rep) for t in batch.local_qset)),
        put(batch.voted, rep),
        put(batch.accepted, rep),
    )

    out_shardings = (dp, rep, rep)
    step = jax.jit(admission_step, out_shardings=out_shardings)
    sig_ok, accept, ratify = step(sharded)
    sig_ok.block_until_ready()
    assert bool(jnp.all(sig_ok)), "sharded verify rejected valid signatures"
    assert sig_ok.sharding.is_equivalent_to(dp, sig_ok.ndim)

    # validator-parallel ballot tally (BASELINE config #5): N simulated
    # validators sharded over the mesh, each tallying with its own qset
    import os

    n_validators = int(os.environ.get("MULTICHIP_VALIDATORS",
                                      str(4 * n_devices)))
    nodes = list(range(n_validators))
    thr = n_validators - n_validators // 3
    qt = Q.build_qset_tensor([(thr, nodes, []) for _ in nodes], nodes)
    rng = np.random.default_rng(11)
    voted = jnp.asarray(rng.random((8, n_validators)) < 0.8)
    accepted = jnp.asarray(rng.random((8, n_validators)) < 0.5)
    qt_s = Q.QSetTensor(*(jax.device_put(t, dp) for t in qt))
    tally = jax.jit(multi_validator_tally, out_shardings=(dp, dp))
    acc, rat = tally(qt_s, jax.device_put(voted, rep),
                     jax.device_put(accepted, rep))
    acc.block_until_ready()
    assert acc.shape == (n_validators, 8)
    assert acc.sharding.is_equivalent_to(dp, acc.ndim)
