"""The ``replay-catchup`` cell's ``correct`` at a test's size, on the
CPU: JAX's default backend patched to read "tpu" (so the node's "auto"
backends resolve to tpu), the device kernel replaced by the plain
reference, checkpoints every 8 ledgers.  A sound run is correct, and
its traced run gives every reader a number; a
kernel that inverts one verdict per dispatch, a device leg that calls
every signature of the checkpoint valid, and the cell's control
(replay with verification skipped) each read not correct."""
import pytest

from benchmark import run
from benchmark.tests.test_faults import fake_kernel

CELL = "replay-catchup"


@pytest.fixture
def small(monkeypatch):
    import jax

    from stellar_core_tpu.ops import ed25519_kernel

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ed25519_kernel, "verify_batch", fake_kernel())
    load_cell = run.load_cell

    def small_cell(name):
        spec, cell, config, traffic = load_cell(name)
        config = dict(config, accounts=40, range_ledgers=8, node=dict(
            config["node"], ARTIFICIALLY_ACCELERATE_TIME_FOR_TESTING=True))
        traffic = dict(traffic, params=dict(
            traffic["params"], txs_per_ledger=40, forge_every=20,
            trace_seconds=3))
        return spec, cell, config, traffic
    monkeypatch.setattr(run, "load_cell", small_cell)


def replay_run(seed, **kw):
    return run.run(CELL, seed, 3.0, False, **kw)


def test_replay_sound(small):
    line = replay_run(2**42 + 1)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["metrics"]["applied_tx_per_s"]["value"] > 0


def test_replay_traced_readers(small):
    """A traced run: every reader of the node's spans, timers and
    counters gives a number (the CPU has no device plane to read), and
    the replayed closes' verify phase only builds the cached verify."""
    line = run.run(CELL, 2**42 + 5, 3.0, True)
    assert line["correct"], line["checks"]
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(m) == {"replay_sigbatch_ms", "replay_close_ms",
                      "replay_prelude_ms", "replay_sigs_per_dispatch",
                      "close_apply_ms", "close_bucket_ms",
                      "close_verify_ms"}, m
    assert m["replay_sigs_per_dispatch"] == 8 * 40
    assert 0 <= m["close_verify_ms"] < m["close_apply_ms"] < \
        m["replay_close_ms"]


def test_replay_kernel_answer_altered(small, monkeypatch):
    from stellar_core_tpu.ops import ed25519_kernel

    monkeypatch.setattr(ed25519_kernel, "verify_batch", fake_kernel("flip"))
    line = replay_run(2**42 + 2)
    assert not line["correct"]
    assert line["checks"]["txs_failed"]["value"] > 0


def test_replay_every_verdict_true(small, monkeypatch):
    """The checkpoint's verdicts all true: forged rows apply."""
    from stellar_core_tpu.herder import tx_set

    monkeypatch.setattr(tx_set, "verify_signature_batch",
                        lambda triples, tracer=None:
                        {t: True for t in triples})
    line = replay_run(2**42 + 3)
    assert not line["correct"]
    assert line["checks"]["txs_failed"]["value"] > 0


def test_replay_control_is_not_correct(small, monkeypatch):
    from stellar_core_tpu.herder import tx_set

    # the control replaces the device leg for the rest of the process
    monkeypatch.setattr(tx_set, "verify_signature_batch",
                        tx_set.verify_signature_batch)
    line = replay_run(2**42 + 4, control=True)
    assert not line["correct"]
    assert line["checks"]["txs_failed"]["value"] > 0
