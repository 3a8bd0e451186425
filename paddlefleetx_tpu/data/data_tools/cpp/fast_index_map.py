"""Ctypes loader for the C++ index-map builders.

Importing this module compiles ``fast_index_map.cpp`` on first use
(one process builds under an exclusive file lock while concurrent
ranks wait on it — the reference's rank-0-compiles-others-spin-wait
protocol, ``gpt_dataset.py:47-69``) and exposes numpy-typed wrappers.
Import failure (no compiler, build error) is the signal for callers
to fall back to the Python builders. The library is rebuilt whenever
it was not built from the present source (``_ensure_built``).
"""

from __future__ import annotations

import ctypes
import fcntl
import os
import subprocess

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_DIR, "libfast_index_map.so")
_SRC = os.path.join(_DIR, "fast_index_map.cpp")
#: sha256 of the source the library was built from (git-ignored)
_STAMP = _SO + ".srchash"


def _src_digest() -> str:
    import hashlib
    with open(_SRC, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _ensure_built() -> str:
    # The freshness check must happen under the lock: an unlocked
    # fast path could dlopen a half-written .so while another rank's
    # compiler is still streaming it out.
    #
    # Fresh means "built from THIS source": the library carries a
    # stamp with the source's sha256. mtimes cannot say so — a copied
    # tree (the chip tool's, a container layer) gives every file the
    # copy's time, and a stale library then looks newer than the
    # source both to a getmtime comparison and to make itself.
    lock_path = os.path.join(_DIR, ".build.lock")
    with open(lock_path, "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)  # one builder; others wait here
        try:
            digest = _src_digest()
            try:
                with open(_STAMP) as f:
                    fresh = os.path.exists(_SO) and \
                        f.read().strip() == digest
            except OSError:
                fresh = False
            if not fresh:
                proc = subprocess.run(["make", "-B", "-C", _DIR],
                                      capture_output=True, text=True)
                if proc.returncode != 0:
                    raise ImportError(
                        "fast_index_map compile failed "
                        f"(exit {proc.returncode}):\n{proc.stderr}")
                with open(_STAMP + ".tmp", "w") as f:
                    f.write(digest + "\n")
                os.replace(_STAMP + ".tmp", _STAMP)
        finally:
            fcntl.flock(lock, fcntl.LOCK_UN)
    return _SO


try:
    _lib = ctypes.CDLL(_ensure_built())
except OSError as e:  # pragma: no cover
    raise ImportError(f"fast_index_map load failed: {e}") from e

_i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
_i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
_u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
_f64p = np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS")

_lib.pfx_build_sample_idx.restype = ctypes.c_int64
_lib.pfx_build_sample_idx.argtypes = [
    _i32p, _i32p, ctypes.c_int32, ctypes.c_int32, ctypes.c_int64,
    ctypes.c_void_p]
_lib.pfx_build_blending_indices.restype = None
_lib.pfx_build_blending_indices.argtypes = [
    _u8p, _i64p, _f64p, ctypes.c_int32, ctypes.c_int64]
_lib.pfx_build_mapping.restype = ctypes.c_int64
_lib.pfx_build_mapping.argtypes = [
    _i64p, ctypes.c_int64, _i32p, ctypes.c_int32, ctypes.c_uint64,
    ctypes.c_int32, ctypes.c_double, ctypes.c_int32, ctypes.c_int32,
    ctypes.c_void_p]
_lib.pfx_build_blocks_mapping.restype = ctypes.c_int64
_lib.pfx_build_blocks_mapping.argtypes = [
    _i64p, ctypes.c_int64, _i32p, _i32p, ctypes.c_int32,
    ctypes.c_uint64, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
    ctypes.c_void_p]


def _ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.c_void_p)


def build_sample_idx(sizes, doc_idx, seq_length, num_epochs,
                     tokens_per_epoch) -> np.ndarray:
    sizes = np.ascontiguousarray(sizes, np.int32)
    doc_idx = np.ascontiguousarray(doc_idx, np.int32)
    n = _lib.pfx_build_sample_idx(sizes, doc_idx, seq_length,
                                  num_epochs, tokens_per_epoch, None)
    out = np.empty((n + 1, 2), np.int32)
    _lib.pfx_build_sample_idx(sizes, doc_idx, seq_length, num_epochs,
                              tokens_per_epoch, _ptr(out))
    return out


def build_blending_indices(num_datasets: int, weights,
                           size: int) -> tuple:
    """Weighted round-robin over datasets: per-sample (dataset index,
    sample-within-dataset index) arrays of length ``size``."""
    if num_datasets > 256:
        raise ValueError(
            f"num_datasets {num_datasets} > 256 (uint8 dataset index)")
    weights = np.ascontiguousarray(weights, np.float64)
    dataset_index = np.empty(size, np.uint8)
    dataset_sample_index = np.empty(size, np.int64)
    _lib.pfx_build_blending_indices(
        dataset_index, dataset_sample_index, weights, num_datasets,
        size)
    return dataset_index, dataset_sample_index


def build_mapping(docs, sizes, num_epochs, max_num_samples,
                  max_seq_length, short_seq_prob, seed,
                  min_num_sent: int = 2) -> np.ndarray:
    """BERT-style [start, end, target-length] sample map (two-pass:
    count with a null pointer, then fill)."""
    docs = np.ascontiguousarray(docs, np.int64)
    sizes = np.ascontiguousarray(sizes, np.int32)
    n_docs = len(docs) - 1
    n = _lib.pfx_build_mapping(
        docs, n_docs, sizes, num_epochs, max_num_samples,
        max_seq_length, short_seq_prob, seed, min_num_sent, None)
    out = np.empty((n, 3), np.int64)
    _lib.pfx_build_mapping(
        docs, n_docs, sizes, num_epochs, max_num_samples,
        max_seq_length, short_seq_prob, seed, min_num_sent, _ptr(out))
    return out


def build_blocks_mapping(docs, sizes, titles_sizes, num_epochs,
                         max_num_samples, max_seq_length, seed,
                         use_one_sent_blocks: bool = False) -> np.ndarray:
    """ICT/retrieval block map: [start, end, doc, block] rows, same
    two-pass count-then-fill protocol as :func:`build_mapping`."""
    docs = np.ascontiguousarray(docs, np.int64)
    sizes = np.ascontiguousarray(sizes, np.int32)
    titles_sizes = np.ascontiguousarray(titles_sizes, np.int32)
    n_docs = len(docs) - 1
    n = _lib.pfx_build_blocks_mapping(
        docs, n_docs, sizes, titles_sizes, num_epochs, max_num_samples,
        max_seq_length, seed, int(use_one_sent_blocks), None)
    out = np.empty((n, 4), np.int64)
    _lib.pfx_build_blocks_mapping(
        docs, n_docs, sizes, titles_sizes, num_epochs, max_num_samples,
        max_seq_length, seed, int(use_one_sent_blocks), _ptr(out))
    return out
