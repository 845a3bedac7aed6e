"""Native (C++) host runtime — the framework's L0 layer.

The reference loads prebuilt C++ engines through ``NativeLoader.java``
(SURVEY.md §1 L1). Here the native library is small (the device compute is
XLA; the host hot loops are hashing/tokenization), builds from source with
g++ on first use, binds via ctypes, and every entry point has a pure-Python
fallback so the package works without a toolchain.

Public surface:
  * ``available()`` — did the library build/load?
  * ``murmur3_batch(names, seed, num_bits)`` — vectorized VW feature hashing
  * ``docs_token_hashes(texts, seed, num_bits, lower)`` — tokenize+hash whole
    documents in one call (TextFeaturizer / VW text path)
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

__all__ = ["available", "build_error", "murmur3_32_native", "murmur3_batch",
           "docs_token_hashes", "bin_rows", "library_path"]

_LOCK = threading.Lock()
_LIB = None
_TRIED = False
_BUILD_ERROR: str | None = None  # why the library is unavailable, if it is

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src",
                    "native_ops.cpp")


def library_path() -> str:
    # keyed by source digest, not mtime: a cached build of an OLDER source
    # (wheel installs preserve mtimes) must never load — a missing symbol
    # would raise out of the ctypes binding instead of falling back
    import hashlib

    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:12]
    cache = os.environ.get("SYNAPSEML_TPU_NATIVE_DIR") or os.path.join(
        os.path.expanduser("~"), ".cache", "synapseml_tpu", "native")
    os.makedirs(cache, exist_ok=True)
    # prune superseded digests, but only STALE ones (>30 days unused):
    # immediate deletion would let two package versions sharing the cache
    # evict each other's builds every startup — or even race a concurrent
    # process between its _build() and CDLL()
    import time

    cutoff = time.time() - 30 * 86400
    for old in os.listdir(cache):
        if (old.startswith("libnative_ops") and old.endswith(".so")
                and digest not in old):
            path = os.path.join(cache, old)
            try:
                if os.path.getmtime(path) < cutoff:
                    os.remove(path)
            except OSError:
                pass
    return os.path.join(cache, f"libnative_ops-{digest}.so")


def _build() -> str | None:
    global _BUILD_ERROR
    try:
        out = library_path()  # content-addressed: existing file IS this source
    except OSError as e:  # source stripped from the install: pure-Python fallback
        _BUILD_ERROR = f"{type(e).__name__}: {e}"
        return None
    if os.path.exists(out):
        return out
    try:
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", _SRC, "-o", out],
            check=True, capture_output=True, timeout=120)
        return out
    except subprocess.CalledProcessError as e:
        _BUILD_ERROR = f"g++ exited {e.returncode}: " \
            f"{e.stderr.decode(errors='replace')[-300:]}"
    except (OSError, subprocess.SubprocessError) as e:
        _BUILD_ERROR = f"{type(e).__name__}: {e}"
    return None


def _load():
    global _LIB, _TRIED
    with _LOCK:
        if _TRIED:
            return _LIB
        _TRIED = True
        path = _build()
        if path is None:
            return None
        try:
            lib = ctypes.CDLL(path)
        except OSError as e:
            global _BUILD_ERROR
            _BUILD_ERROR = f"{type(e).__name__}: {e}"
            return None
        lib.nat_murmur3_32.restype = ctypes.c_uint32
        lib.nat_murmur3_32.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                       ctypes.c_uint32]
        lib.nat_murmur3_batch.restype = None
        lib.nat_murmur3_batch.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
            ctypes.c_uint32, ctypes.c_uint32, ctypes.POINTER(ctypes.c_uint32)]
        lib.nat_docs_token_hashes.restype = None
        lib.nat_docs_token_hashes.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
            ctypes.c_uint32, ctypes.c_uint32, ctypes.c_int32,
            ctypes.POINTER(ctypes.c_uint32), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64)]
        lib.nat_bin_rows.restype = None
        lib.nat_bin_rows.argtypes = [
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_double),
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int32,
            ctypes.c_int32, ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int32]
        _LIB = lib
        return _LIB


def available() -> bool:
    return _load() is not None


def build_error() -> str | None:
    """Why :func:`available` is False (compiler output / load error), or
    None when the library loaded."""
    _load()
    return _BUILD_ERROR


def murmur3_32_native(data: bytes, seed: int = 0) -> int | None:
    lib = _load()
    if lib is None:
        return None
    return int(lib.nat_murmur3_32(data, len(data), seed & 0xFFFFFFFF))


def _pack(strings: list[bytes]) -> tuple[bytes, np.ndarray]:
    offsets = np.zeros(len(strings) + 1, np.int64)
    np.cumsum([len(s) for s in strings], out=offsets[1:])
    return b"".join(strings), offsets


def murmur3_batch(names: list[str], seed: int = 0, num_bits: int = 32) -> np.ndarray | None:
    """n feature names -> n masked hashes; None when the library is absent."""
    lib = _load()
    if lib is None:
        return None
    data, offsets = _pack([n.encode("utf-8") for n in names])
    out = np.zeros(len(names), np.uint32)
    mask = (1 << num_bits) - 1 if num_bits < 32 else 0xFFFFFFFF
    lib.nat_murmur3_batch(
        data, offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(names), seed & 0xFFFFFFFF, mask,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)))
    return out


def docs_token_hashes(texts: list[str], seed: int = 0, num_bits: int = 18,
                      lower: bool = True, max_tokens_per_doc: int = 4096):
    """Tokenize+hash documents natively -> list of per-doc bucket arrays;
    None when the library is absent."""
    lib = _load()
    if lib is None:
        return None
    data, offsets = _pack([t.encode("utf-8") for t in texts])
    n = len(texts)
    out = np.zeros(n * max_tokens_per_doc, np.uint32)
    counts = np.zeros(n, np.int64)
    mask = (1 << num_bits) - 1 if num_bits < 32 else 0xFFFFFFFF
    lib.nat_docs_token_hashes(
        data, offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), n,
        seed & 0xFFFFFFFF, mask, 1 if lower else 0,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        max_tokens_per_doc,
        counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
    return [out[i * max_tokens_per_doc : i * max_tokens_per_doc + counts[i]].copy()
            for i in range(n)]


def bin_rows(x: np.ndarray, boundaries: np.ndarray, nan_bin: int, max_bin: int,
             categorical: tuple = (), n_threads: int | None = None):
    """Row-major multithreaded binning (the GBDT Dataset-construction hot
    loop; reference analog: the Swig marshaling behind
    ``LGBM_DatasetPushRowsWithMetadata``). searchsorted-right semantics per
    column; NaN -> ``nan_bin``; categorical columns bin by identity. Returns
    (N, F) int32, or None when the library is absent."""
    lib = _load()
    if lib is None:
        return None
    xf = np.ascontiguousarray(x, dtype=np.float32)
    n, f = xf.shape
    bounds = np.ascontiguousarray(boundaries, dtype=np.float64)
    if bounds.ndim != 2 or bounds.shape[0] != f:
        raise ValueError(f"boundaries shape {bounds.shape} does not match "
                         f"feature count {f}")
    is_cat = np.zeros(f, np.uint8)
    if categorical:
        idx = np.asarray(categorical, np.int64)
        if idx.size and (idx.min() < 0 or idx.max() >= f):
            raise ValueError(f"categorical indices {sorted(categorical)} out "
                             f"of range [0, {f})")
        is_cat[idx] = 1
    out = np.empty((n, f), np.int32)
    if n_threads is None:
        n_threads = min(os.cpu_count() or 1, 16)
    lib.nat_bin_rows(
        xf.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        bounds.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        n, f, bounds.shape[1], nan_bin, max_bin,
        is_cat.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), n_threads)
    return out
