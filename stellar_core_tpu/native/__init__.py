"""Native (C++) runtime components behind ctypes seams
(the reference's C++ runtime tier — SURVEY.md §7 architecture stance:
host-side merge/scan compute stays native; JAX/Pallas is the device
tier).

The libraries build on first use with g++ from the committed sources
and are cached next to them (gitignored, never committed); every caller
has a pure-Python fallback, so a missing toolchain degrades gracefully.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

from ..utils.lockdep import register_lock

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_DIR, "_native.so")
_SRCS = [os.path.join(_DIR, f) for f in ("bucket_merge.cpp",
                                         "quorum_enum.cpp")]

_lock = register_lock(threading.Lock(), "native.lib")
_lib: Optional[ctypes.CDLL] = None  # guarded-by: _lock
_tried = False  # guarded-by: _lock


def _src_digest(srcs) -> str:
    import hashlib

    h = hashlib.sha256()
    for s in srcs:
        with open(s, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _install(tmp: str, so: str, srcs) -> None:
    """Move a freshly built ``tmp`` into place as ``so``, then its
    sidecar: a reader between the two sees a stale sidecar and rebuilds,
    never a fresh sidecar over an old .so.  Each step is an atomic
    replace of a pid-unique temp, so concurrent first builds (test
    workers) cannot interleave into a torn file."""
    side = f"{so}.srchash.{os.getpid()}.tmp"
    with open(side, "w") as f:
        f.write(_src_digest(srcs))
    os.replace(tmp, so)
    os.replace(side, so + ".srchash")


def _stale(srcs, so: str) -> bool:
    """Content-hash staleness: each built .so carries a ``.srchash``
    sidecar recording its sources' digest.  mtimes are useless here — a
    source edit without a rebuild could win the mtime race and load an
    outdated consensus kernel silently."""
    if not os.path.exists(so):
        return True
    try:
        with open(so + ".srchash") as f:
            return f.read().strip() != _src_digest(srcs)
    except OSError:
        return True


def _build() -> bool:
    tmp = f"{_SO}.{os.getpid()}.tmp"
    try:
        r = subprocess.run(
            ["g++", "-O2", "-shared", "-fPIC", "-o", tmp] + _SRCS,
            capture_output=True, timeout=120)
        if r.returncode != 0:
            return False
        _install(tmp, _SO, _SRCS)
        return True
    except Exception:
        return False


def get_lib() -> Optional[ctypes.CDLL]:
    """The native library, building it on first use; None when
    unavailable (callers fall back to Python)."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if _stale(_SRCS, _SO):
            if not _build():
                return None
        try:
            lib = ctypes.CDLL(_SO)
        except OSError:
            return None
        lib.bucket_merge.restype = ctypes.c_int64
        lib.bucket_merge.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int64,
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int32),
        ]
        p64 = ctypes.POINTER(ctypes.c_int64)
        p32 = ctypes.POINTER(ctypes.c_int32)
        pu8 = ctypes.POINTER(ctypes.c_uint8)
        lib.bucket_merge_stream.restype = ctypes.c_int64
        lib.bucket_merge_stream.argtypes = [
            ctypes.c_char_p, p64, p32,        # new stream/eoff/elen
            ctypes.c_char_p, p64, p32, p32,   # new keys/koff/klen/types
            ctypes.c_int64,                   # n_new
            ctypes.c_char_p, p64, p32,        # old stream/eoff/elen
            ctypes.c_char_p, p64, p32, p32,   # old keys/koff/klen/types
            ctypes.c_int64,                   # n_old
            ctypes.c_char_p,                  # out_path (NULL = no file)
            p64, p32, p32,                    # out eoff/elen/types
            pu8, p64, p32,                    # out keys/koff/klen
            pu8, p64,                         # out_hash32, out_bytes
        ]
        lib.quorum_enum_check.restype = ctypes.c_int64
        lib.quorum_enum_check.argtypes = [
            ctypes.c_int32,                      # n_nodes
            ctypes.POINTER(ctypes.c_int32),      # top_thr [n]
            ctypes.POINTER(ctypes.c_uint64),     # top_mem [n*W]
            ctypes.POINTER(ctypes.c_int32),      # inner_off [n+1]
            ctypes.POINTER(ctypes.c_int32),      # inner_thr [total]
            ctypes.POINTER(ctypes.c_uint64),     # inner_mem [total*W]
            ctypes.POINTER(ctypes.c_int32),      # interrupt flag (polled)
            ctypes.c_int64,                      # max_calls (0 = unlimited)
            ctypes.POINTER(ctypes.c_uint64),     # out_q1 [W]
            ctypes.POINTER(ctypes.c_uint64),     # out_q2 [W]
            ctypes.POINTER(ctypes.c_int64),      # out_calls
        ]
        lib.bucket_lower_bound.restype = None
        lib.bucket_lower_bound.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int64,
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64),
        ]
        pu64 = ctypes.POINTER(ctypes.c_uint64)
        lib.bloom_fill.restype = None
        lib.bloom_fill.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int64,
            pu64, ctypes.c_int64,
        ]
        lib.bloom_check.restype = None
        lib.bloom_check.argtypes = [
            pu64, ctypes.c_int64,
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int32),
        ]
        _lib = lib
        return _lib


# -- native XDR packer (CPython extension) -------------------------------

_XDRPACK_SRC = os.path.join(_DIR, "xdr_pack.c")
_XDRPACK_SO = os.path.join(_DIR, "_xdrpack.so")
_xdrpack_mod = None  # guarded-by: _lock
_xdrpack_tried = False  # guarded-by: _lock


def _build_extension(src: str, so: str) -> bool:
    """Compile one CPython extension source to ``so``; pid-unique tmp +
    atomic replace so concurrent first-builds can never interleave into
    one file and install a torn .so.  Shared by the xdrpack encoder and
    the apply kernel."""
    import sysconfig

    inc = sysconfig.get_paths()["include"]
    tmp = f"{so}.{os.getpid()}.tmp"
    try:
        r = subprocess.run(
            ["g++", "-O2", "-shared", "-fPIC", "-I", inc, "-o", tmp, src],
            capture_output=True, timeout=180)
        if r.returncode != 0:
            return False
        _install(tmp, so, [src])
        return True
    except Exception:
        return False


def _load_extension(name: str, so: str):
    import importlib.util

    spec = importlib.util.spec_from_file_location(name, so)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ext_cached(name: str, src: str, so: str, mod, tried, build: bool):
    """The one caching contract for the CPython-extension kernels
    (caller holds ``_lock`` and passes/stores its module-level handle
    pair): one-shot ``tried`` semantics, the ``build=False`` early
    return that leaves a later ``build=True`` caller free to succeed,
    and content-hash staleness.  Returns the updated ``(mod, tried)``
    pair — keeping this logic in one place so a fix to the contract
    cannot drift between the extensions."""
    if mod is not None or tried:
        return mod, tried
    try:
        if _stale([src], so):
            if not build:
                return None, False  # not tried: build=True may succeed
            tried = True
            if not _build_extension(src, so):
                return None, True
        else:
            tried = True
        mod = _load_extension(name, so)
    except Exception:
        return None, True
    return mod, tried


# -- native apply kernel (CPython extension; see apply_kernel.cpp) -------

_APPLY_SRC = os.path.join(_DIR, "apply_kernel.cpp")
_APPLY_SO = os.path.join(_DIR, "_applykernel.so")
_applykernel_mod = None  # guarded-by: _lock
_applykernel_tried = False  # guarded-by: _lock


def get_apply_kernel(build: bool = True):
    """The _applykernel extension (GIL-free transaction-apply kernel);
    builds on first use, None when unavailable — callers fall back to
    the Python reference apply."""
    global _applykernel_mod, _applykernel_tried
    with _lock:
        _applykernel_mod, _applykernel_tried = _ext_cached(
            "_applykernel", _APPLY_SRC, _APPLY_SO,
            _applykernel_mod, _applykernel_tried, build)
        return _applykernel_mod


def get_xdrpack(build: bool = True):
    """The _xdrpack extension module (schema-driven XDR encoder); with
    ``build=False`` only an already-built fresh .so is loaded (imports
    stay cheap — node startup triggers the build).  None when
    unavailable."""
    global _xdrpack_mod, _xdrpack_tried
    with _lock:
        _xdrpack_mod, _xdrpack_tried = _ext_cached(
            "_xdrpack", _XDRPACK_SRC, _XDRPACK_SO,
            _xdrpack_mod, _xdrpack_tried, build)
        return _xdrpack_mod
