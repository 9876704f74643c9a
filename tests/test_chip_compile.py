"""AOT compiles of the served path's device kernels for a described TPU
v5e chip, with no chip attached (on-chip-measurement guide, section 2).

The TPU compiler refuses here what it would refuse on the chip: a Pallas
block the tiling cannot hold, a program too large for the device, a
kernel that does not lower.  Nothing runs, so these say nothing about
results or speed; ``chip_smoke.py`` is the run on the chip.

The topology is described inside a module fixture, never at import: one
process at a time may load the TPU library, and every xdist worker
imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

N_VALIDATORS = 64  # BASELINE config #5
N_ORGS = 16        # simulation.tiered_qset at 4 validators per org


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    prev_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", prev_cache)
        compilation_cache.reset_cache()
        if prev_log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = prev_log_dir


def _sig_batch(n, sharding):
    return [jax.ShapeDtypeStruct((n, w), jnp.uint8, sharding=sharding)
            for w in (32, 64, 32)]


@pytest.mark.parametrize("bucket", [256, 1024])
def test_pallas_verify_compiles_for_v5e(one_chip, bucket):
    from stellar_core_tpu.ops.ed25519_pallas import verify_batch

    compiled = verify_batch.lower(*_sig_batch(bucket, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _while_bodies(hlo_text):
    """{name: instruction lines} of every computation a while loop runs."""
    bodies = set(re.findall(r"body=%?([\w.\-]+)", hlo_text))
    comps, name = {}, None
    for line in hlo_text.splitlines():
        head = re.match(r"%?([\w.\-]+) .*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name:
            comps[name].append(line)
    return {b: comps[b] for b in bodies}


def test_xla_verify_compiles_for_v5e(one_chip):
    from stellar_core_tpu.ops.ed25519_kernel import verify_batch

    compiled = verify_batch.lower(*_sig_batch(256, one_chip)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes > 0
    # the ladder, the body with the most instructions, runs 64 times: each
    # op in it is paid per step, so the field multiply has to fuse
    ladder = max(_while_bodies(compiled.as_text()).values(), key=len)
    updates = sum(" dynamic-update-slice(" in line for line in ladder)
    fusions = sum(" fusion(" in line for line in ladder)
    assert updates == 0, f"{updates} dynamic-update-slice ops in the ladder"
    assert fusions < 700, f"{fusions} fusions in the ladder"


def _qsets(sharding, batch=()):
    from stellar_core_tpu.ops.quorum import QSetTensor

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(batch + shape, dtype, sharding=sharding)

    return QSetTensor(s((N_VALIDATORS,), jnp.bool_), s((), jnp.int32),
                      s((N_ORGS, N_VALIDATORS), jnp.bool_),
                      s((N_ORGS,), jnp.int32))


@pytest.mark.parametrize("kernel", ["federated_ratify", "is_v_blocking",
                                    "contract_batch"])
def test_quorum_kernel_compiles_for_v5e(one_chip, kernel):
    from stellar_core_tpu.ops import quorum as Q

    local = _qsets(one_chip)
    qsets = _qsets(one_chip, (N_VALIDATORS,))
    members = jax.ShapeDtypeStruct((N_VALIDATORS, N_VALIDATORS), jnp.bool_,
                                   sharding=one_chip)
    args = {"federated_ratify": (local, qsets, members),
            "is_v_blocking": (local, members),
            "contract_batch": (qsets, members)}[kernel]
    compiled = jax.jit(getattr(Q, kernel)).lower(*args).compile()
    assert compiled.as_text()
