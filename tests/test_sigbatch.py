"""Cross-peer SCP signature-batch admission (ISSUE 7 satellite,
ROADMAP 4 companion): flooded envelopes received within one crank
verify as ONE padded batch (overlay/manager.py _drain_scp_inbox)
instead of per-envelope inside SCP.

The property: verdicts are identical either way — batching is a pure
dispatch-shape change.  Consensus must close the same ledgers with the
same hashes with OVERLAY_SIG_BATCH on and off, forged signatures must
still be rejected through the batched path, and the batch counters
must surface in /metrics.
"""
import pytest

from stellar_core_tpu.crypto import SecretKey, sha256
from stellar_core_tpu.simulation import core
from stellar_core_tpu.xdr import types as T

from .test_simulation import settle


def _run_network(n_rounds=3, **config_kw):
    """core-3 network closing ``n_rounds`` ledgers; returns (sim,
    per-round ledger hashes)."""
    sim = core(3, **config_kw)
    sim.start_all_nodes()
    settle(sim)
    hashes = []
    for _ in range(n_rounds):
        assert sim.close_ledger()
        sim.assert_in_sync()
        hashes.append(sim.ledger_hashes()[0])
    return sim, hashes


def test_sigbatch_engages_and_counters_surface():
    """Default-on batching: a consensus round floods envelopes, so every
    node must have verified at least one multi-envelope batch, counted
    under overlay.sigbatch.* in the metrics registry."""
    sim, _ = _run_network()
    for app in sim.nodes.values():
        batches = app.metrics.counter("overlay.sigbatch.batches").count
        envs = app.metrics.counter("overlay.sigbatch.envelopes").count
        assert batches > 0, "sig batching never engaged"
        assert envs >= batches
        snap = app.metrics.snapshot()
        assert snap["overlay.sigbatch.batches"]["count"] == batches


def test_sigbatch_off_parity():
    """OVERLAY_SIG_BATCH=0 restores the per-envelope path; the network
    must close the exact same ledger hashes (verdict identity)."""
    _, batched = _run_network()
    sim_off, direct = _run_network(OVERLAY_SIG_BATCH=False)
    for app in sim_off.nodes.values():
        assert app.metrics.counter(
            "overlay.sigbatch.batches").count == 0
    assert batched == direct


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_verify_triples_matches_scalar_verdicts(backend):
    """_verify_triples is the batch chokepoint: good and forged
    signatures interleaved must come back [True, False, ...] exactly
    like scalar verify_sig, on the host and down the device leg (JAX's
    CPU backend here)."""
    sim = core(2)
    app = next(iter(sim.nodes.values()))
    app.config.CRYPTO_BACKEND = backend
    om = app.overlay_manager
    sk = SecretKey(b"\x07" * 32)
    msg_a, msg_b = sha256(b"batch a"), sha256(b"batch b")
    good_a = (sk.public_key().raw, sk.sign(msg_a), msg_a)
    good_b = (sk.public_key().raw, sk.sign(msg_b), msg_b)
    forged = (sk.public_key().raw, sk.sign(msg_a), msg_b)
    assert om._verify_triples([good_a, forged, good_b]) == \
        [True, False, True]


def test_forged_envelope_rejected_through_batch_path():
    """End-to-end through the drain: a properly-signed envelope primes a
    True verdict; tampering the signature primes False and SCP refuses
    the envelope — the batch path must never weaken admission."""
    sim = core(2)
    sim.start_all_nodes()
    settle(sim)
    assert sim.close_ledger()
    a, b = list(sim.nodes)
    app = sim.nodes[a]
    om, driver = app.overlay_manager, app.herder.driver
    # a real envelope from the other validator, captured post-consensus
    slot_idx = max(app.herder.scp.slots)
    env = next(
        e for e in app.herder.scp.get_current_state_envelopes(slot_idx)
        if e.statement.nodeID.value == b)
    good = driver.envelope_sig_triple(env)
    forged_env = T.SCPEnvelope.make(statement=env.statement,
                                    signature=bytes(64))
    forged = driver.envelope_sig_triple(forged_env)
    om._scp_inbox.extend([env, forged_env])
    om._drain_scp_inbox()
    assert driver._sig_verdicts[good] is True
    assert driver._sig_verdicts[forged] is False
    assert driver.verify_envelope(forged_env) is False
    assert driver.verify_envelope(env) is True


def test_sigbatch_skips_out_of_bracket_envelopes():
    """Stale/far-future envelopes are discarded unverified by the
    herder; the drain must not spend batch slots on them."""
    sim = core(2)
    sim.start_all_nodes()
    settle(sim)
    assert sim.close_ledger()
    app = next(iter(sim.nodes.values()))
    om, herder = app.overlay_manager, app.herder
    slot_idx = max(herder.scp.slots)
    env = herder.scp.get_current_state_envelopes(slot_idx)[0]
    far_future = env.statement.slotIndex + 10_000
    stale = T.SCPEnvelope.make(
        statement=env.statement._replace(slotIndex=far_future),
        signature=env.signature)
    before = app.metrics.counter("overlay.sigbatch.envelopes").count
    om._scp_inbox.append(stale)
    om._drain_scp_inbox()
    triple = herder.driver.envelope_sig_triple(stale)
    assert triple not in herder.driver._sig_verdicts
    assert app.metrics.counter("herder.scp.discarded").count > 0
