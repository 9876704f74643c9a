"""Device-tier plumbing: backend resolution, the persistent compile
cache, and the fixed signature-batch buckets.

``"auto"`` backends resolve in the node's own process from
``jax.default_backend()``: the device kernels on a TPU, the host tiers
everywhere else.  Nothing here starts a process or falls back in
silence; the resolved backend is logged at start and served by ``/info``.
"""
from __future__ import annotations

import os
from typing import Tuple

#: the compile cache used when ``JAX_COMPILATION_CACHE_DIR`` is unset: one
#: fixed, gitignored directory inside the checkout (the path is part of
#: the cache key, so it never names a pid, a time or a temp dir)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def resolve_auto_backends() -> Tuple[str, str]:
    """(CRYPTO_BACKEND, SCP_TALLY_BACKEND) that ``"auto"`` means in this
    process: ("tpu", "tensor") when JAX's default backend is a TPU,
    ("cpu", "host") otherwise."""
    import jax

    if jax.default_backend() == "tpu":
        return "tpu", "tensor"
    return "cpu", "host"


def enable_compilation_cache() -> str:
    """Point JAX's persistent compilation cache at
    ``JAX_COMPILATION_CACHE_DIR`` when it is set, else at
    ``DEFAULT_CACHE_DIR``; every compile is cached, however short.
    Returns the directory.  Call before the first compile: JAX fixes the
    cache on its first use (and creates the directory then)."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or DEFAULT_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


# Fixed signature-batch sizes: every device verify pads its batch up to
# one of these, so admission traffic cannot present a new shape per close
# and trigger a recompile mid-run.  Shapes are MXU-friendly powers of
# two; beyond the largest bucket, batches round up to its multiple.
SIG_BATCH_BUCKETS = (256, 512, 1024, 2048, 4096, 8192, 16384, 32768,
                     65536, 131072)


def pad_signature_batch(n: int) -> int:
    """Smallest allowed batch size >= n."""
    if n <= 0:
        return SIG_BATCH_BUCKETS[0]
    for b in SIG_BATCH_BUCKETS:
        if n <= b:
            return b
    top = SIG_BATCH_BUCKETS[-1]
    return ((n + top - 1) // top) * top
