"""ctypes bindings to the native C++ host kernels (``native/fastio.cpp``).

Reference counterpart: the JVM/native machinery under Spark (netty, Tungsten,
codec JNI — SURVEY.md §2 native-code note).  The rebuild's device-side native
layer is XLA itself; this module is the *host*-side native layer: the
tokenizer+hasher and edge-list parser, the two ingest loops SURVEY.md §7
flags as Python bottlenecks at Wikipedia / soc-LiveJournal1 scale.

Every entry point degrades gracefully: if the shared library is missing and
cannot be built (no g++), callers get ``None`` and fall back to the numpy
implementations — bit-identical results, just slower.  ``tests/test_native.py``
pins C++ == numpy on the same inputs.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
import threading

import numpy as np

_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC = os.path.join(_HERE, "native", "fastio.cpp")
_BUILD_DIR = os.path.join(_HERE, "native", "build")
# Portable flags (no -march=native): the tree may be copied to a host with
# another CPU.
_CXX_FLAGS = ("-O3", "-shared", "-fPIC", "-std=c++17")

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_lib_failed = False


def library_path() -> str:
    """The library built from the current ``fastio.cpp`` with
    ``_CXX_FLAGS``: its name carries a hash of both, so a library built
    from other source or flags is never loaded."""
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    h.update(" ".join(_CXX_FLAGS).encode())
    return os.path.join(_BUILD_DIR, f"libfastio-{h.hexdigest()[:16]}.so")


def _load() -> ctypes.CDLL | None:
    """Build (once) and load the shared library; None if unavailable."""
    global _lib, _lib_failed
    if _lib is not None or _lib_failed:
        return _lib
    with _lock:
        if _lib is not None or _lib_failed:
            return _lib
        try:
            if not os.path.exists(_SRC):
                _lib_failed = True
                return None
            lib_path = library_path()
            if not os.path.exists(lib_path):
                os.makedirs(_BUILD_DIR, exist_ok=True)
                # build beside the target, then rename: another process
                # loading the same key never sees a half-written library
                fd, tmp = tempfile.mkstemp(suffix=".so", dir=_BUILD_DIR)
                os.close(fd)
                try:
                    subprocess.run(  # graftlint: disable=blocking-under-lock (build-once guard: the lock is held across the g++ build ON PURPOSE so concurrent loaders wait for one build instead of racing duplicate compilers)
                        ["g++", *_CXX_FLAGS, _SRC, "-o", tmp],
                        check=True,
                        capture_output=True,
                    )
                    os.replace(tmp, lib_path)
                finally:
                    if os.path.exists(tmp):
                        os.unlink(tmp)
            lib = ctypes.CDLL(lib_path)
            _declare(lib)
            _lib = lib
        except (OSError, subprocess.CalledProcessError):
            _lib_failed = True
    return _lib


def _declare(lib: ctypes.CDLL) -> None:
    c_i64 = ctypes.c_int64
    p_u8 = ctypes.POINTER(ctypes.c_uint8)
    p_i32 = ctypes.POINTER(ctypes.c_int32)
    p_i64 = ctypes.POINTER(ctypes.c_int64)

    lib.parse_edges_count.argtypes = [p_u8, c_i64]
    lib.parse_edges_count.restype = c_i64
    lib.parse_edges_fill.argtypes = [p_u8, c_i64, p_i64, p_i64]
    lib.parse_edges_fill.restype = c_i64

    lib.sort_dedup_edges.argtypes = [p_i64, p_i64, c_i64, c_i64]
    lib.sort_dedup_edges.restype = c_i64

    lib.tokenize_hash_count.argtypes = [p_u8, c_i64, p_i64, c_i64, c_i64, c_i64, c_i64]
    lib.tokenize_hash_count.restype = c_i64
    lib.tokenize_hash_fill.argtypes = [
        p_u8, c_i64, p_i64, c_i64, c_i64, c_i64, c_i64, c_i64, p_i32, p_i32, p_i32,
    ]
    lib.tokenize_hash_fill.restype = c_i64


def available() -> bool:
    return _load() is not None


def parse_edge_file(path: str) -> np.ndarray | None:
    """SNAP edge file → int64 [E, 2] array of (src, dst); None if native
    layer unavailable (caller falls back to numpy parse)."""
    lib = _load()
    if lib is None:
        return None
    with open(path, "rb") as f:
        data = np.frombuffer(f.read(), dtype=np.uint8)
    if data.size == 0:
        return np.empty((0, 2), dtype=np.int64)
    buf = data.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
    n = lib.parse_edges_count(buf, data.size)
    if n < 0:
        return None
    src = np.empty(n, dtype=np.int64)
    dst = np.empty(n, dtype=np.int64)
    got = lib.parse_edges_fill(
        buf, data.size,
        src.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        dst.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
    )
    if got != n:
        return None
    return np.stack([src, dst], axis=1)


def sort_dedup_edges(
    src: np.ndarray, dst: np.ndarray, *, dedup: bool = True
) -> tuple[np.ndarray, np.ndarray] | None:
    """(dst, src)-radix-sort + optional dedup of compacted int64 edge arrays
    in C++ (the graph-builder hot step); None if the native layer is
    unavailable or ids exceed 2^31 (caller falls back to np.lexsort).

    MUTATES ``src``/``dst`` in place when they are already contiguous int64
    (the from_edges call site owns fresh astype copies; at soc-LiveJournal1
    scale a defensive copy would be an extra ~1 GB).  On failure (-1) the
    inputs are untouched — validation happens before any write."""
    lib = _load()
    if lib is None or src.size == 0:
        return None
    src_c = np.ascontiguousarray(src, dtype=np.int64)
    dst_c = np.ascontiguousarray(dst, dtype=np.int64)
    p_i64 = ctypes.POINTER(ctypes.c_int64)
    n = lib.sort_dedup_edges(
        src_c.ctypes.data_as(p_i64), dst_c.ctypes.data_as(p_i64),
        src_c.size, int(dedup),
    )
    if n < 0:
        return None
    return src_c[:n], dst_c[:n]


def tokenize_and_hash(
    docs,
    *,
    vocab_bits: int,
    ngram: int,
    lowercase: bool,
    min_token_len: int,
) -> tuple[np.ndarray, np.ndarray, np.ndarray] | None:
    """Tokenize + FNV-1a-hash a batch of docs in C++.

    Returns (doc_ids int32 [T], term_ids int32 [T], doc_lengths int32 [D])
    matching the numpy path in io/text.py exactly, or None if unavailable.
    """
    lib = _load()
    if lib is None:
        return None
    enc = [d.encode("utf-8") for d in docs]
    lens = np.fromiter((len(b) for b in enc), dtype=np.int64, count=len(enc))
    blob = np.frombuffer(b"".join(enc), dtype=np.uint8) if enc else np.empty(0, np.uint8)
    # Guard ctypes against NULL data pointers from zero-length arrays.
    blob = np.ascontiguousarray(blob) if blob.size else np.zeros(1, np.uint8)
    lens_c = np.ascontiguousarray(lens) if lens.size else np.zeros(1, np.int64)

    p_u8 = ctypes.POINTER(ctypes.c_uint8)
    p_i32 = ctypes.POINTER(ctypes.c_int32)
    p_i64 = ctypes.POINTER(ctypes.c_int64)

    total = lib.tokenize_hash_count(
        blob.ctypes.data_as(p_u8), int(blob.size if enc else 0),
        lens_c.ctypes.data_as(p_i64), len(enc),
        int(ngram), int(lowercase), int(min_token_len),
    )
    if total < 0:
        return None
    doc_ids = np.empty(total, dtype=np.int32)
    term_ids = np.empty(total, dtype=np.int32)
    doc_lengths = np.empty(max(len(enc), 1), dtype=np.int32)
    got = lib.tokenize_hash_fill(
        blob.ctypes.data_as(p_u8), int(blob.size if enc else 0),
        lens_c.ctypes.data_as(p_i64), len(enc),
        int(ngram), int(lowercase), int(min_token_len), int(vocab_bits),
        doc_ids.ctypes.data_as(p_i32) if total else ctypes.cast(None, p_i32),
        term_ids.ctypes.data_as(p_i32) if total else ctypes.cast(None, p_i32),
        doc_lengths.ctypes.data_as(p_i32),
    )
    if got != total:
        return None
    return doc_ids, term_ids, doc_lengths[: len(enc)]
