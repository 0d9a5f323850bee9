"""ctypes binding for the native bucketizer (lazy-built with g++).

Drop-in fast path for :func:`tpu_als.core.ratings.build_csr_buckets`: the
two O(nnz) blocking passes (per-entity counting, padded-bucket fill) run in
threaded C++ instead of numpy argsort machinery, producing bit-identical
buckets.  See native/bucketize.cc for the role this plays vs the reference
stack's JVM blocking code (SURVEY.md §2.B4).
"""

from __future__ import annotations

import ctypes
import os
import subprocess

import numpy as np

from tpu_als.io._native_build import build_native

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), "native")
_SRC = os.path.join(_NATIVE_DIR, "bucketize.cc")
_LIB = os.path.join(_NATIVE_DIR, "libbucketize.so")

_lib = None
_load_failed = False
_I64P = ctypes.POINTER(ctypes.c_int64)
_I32P = ctypes.POINTER(ctypes.c_int32)
_F32P = ctypes.POINTER(ctypes.c_float)


def load():
    global _lib
    if _lib is not None:
        return _lib
    build_native(_SRC, _LIB, extra_flags=("-pthread",))
    lib = ctypes.CDLL(_LIB)
    lib.bucketize_count.restype = None
    lib.bucketize_count.argtypes = [
        _I64P, ctypes.c_int64, ctypes.c_int64, _I64P, ctypes.c_int]
    lib.bucketize_fill.restype = None
    lib.bucketize_fill.argtypes = [
        _I64P, _I64P, _F32P, ctypes.c_int64, ctypes.c_int64,
        _I64P,
        _I32P, ctypes.c_int32, _I64P,
        ctypes.POINTER(_I32P), ctypes.POINTER(_I32P),
        ctypes.POINTER(_F32P), ctypes.POINTER(_F32P),
        _I32P, _I32P, ctypes.c_int]
    _lib = lib
    return lib


def available():
    global _load_failed
    if _load_failed:
        return False
    try:
        load()
        return True
    except (OSError, subprocess.CalledProcessError):
        _load_failed = True  # don't re-spawn a failing g++ per call
        return False


def counts(row_idx, num_rows, n_threads=None):
    """Per-entity rating counts (np.bincount equivalent).

    Bounds-checks the indices before handing them to C++ — out-of-range
    rows (e.g. the -1 'missing' sentinel from IdMap.to_dense) must raise
    like the numpy path, not corrupt the heap.
    """
    lib = load()
    if n_threads is None:
        n_threads = min(16, os.cpu_count() or 1)
    row_idx = np.ascontiguousarray(row_idx, dtype=np.int64)
    if len(row_idx):
        lo, hi = row_idx.min(), row_idx.max()
        if lo < 0 or hi >= num_rows:
            raise ValueError(
                f"row indices must be in [0, {num_rows}); got range "
                f"[{lo}, {hi}]")
    out = np.empty(num_rows, dtype=np.int64)
    lib.bucketize_count(
        row_idx.ctypes.data_as(_I64P), len(row_idx), num_rows,
        out.ctypes.data_as(_I64P), n_threads)
    return out


def fill_buckets(row_idx, col_idx, vals, num_rows, cnts, ebucket,
                 bucket_layout, n_threads=None):
    """Fill pre-sized bucket arrays.

    ebucket: [num_rows] int32 bucket index per entity, -1 for entities with
    no ratings — computed by the caller with the same width rule as the
    numpy path (single source of truth for bucket assignment).
    bucket_layout: list of (width, nb, nb_pad) ascending by width, with
    ``nb`` = rated entities of that width and ``nb_pad`` >= nb the padded
    row count.  Returns list of (rows, cols, vals, mask) numpy arrays.
    """
    lib = load()
    if n_threads is None:
        n_threads = min(16, os.cpu_count() or 1)
    row_idx = np.ascontiguousarray(row_idx, dtype=np.int64)
    col_idx = np.ascontiguousarray(col_idx, dtype=np.int64)
    vals = np.ascontiguousarray(vals, dtype=np.float32)
    cnts = np.ascontiguousarray(cnts, dtype=np.int64)
    ebucket = np.ascontiguousarray(ebucket, dtype=np.int32)
    nnz = len(row_idx)
    widths = np.array([w for w, _, _ in bucket_layout], dtype=np.int64)

    out = []
    rows_ptrs = (_I32P * len(bucket_layout))()
    cols_ptrs = (_I32P * len(bucket_layout))()
    vals_ptrs = (_F32P * len(bucket_layout))()
    mask_ptrs = (_F32P * len(bucket_layout))()
    for b, (w, nb, nb_pad) in enumerate(bucket_layout):
        rows = np.full(nb_pad, num_rows, dtype=np.int32)
        cols = np.zeros((nb_pad, w), dtype=np.int32)
        v = np.zeros((nb_pad, w), dtype=np.float32)
        m = np.zeros((nb_pad, w), dtype=np.float32)
        out.append((rows, cols, v, m))
        rows_ptrs[b] = rows.ctypes.data_as(_I32P)
        cols_ptrs[b] = cols.ctypes.data_as(_I32P)
        vals_ptrs[b] = v.ctypes.data_as(_F32P)
        mask_ptrs[b] = m.ctypes.data_as(_F32P)

    elocal = np.empty(num_rows, dtype=np.int32)
    cursor = np.zeros(num_rows, dtype=np.int32)
    lib.bucketize_fill(
        row_idx.ctypes.data_as(_I64P), col_idx.ctypes.data_as(_I64P),
        vals.ctypes.data_as(_F32P), nnz, num_rows,
        cnts.ctypes.data_as(_I64P),
        ebucket.ctypes.data_as(_I32P), len(bucket_layout),
        widths.ctypes.data_as(_I64P),
        rows_ptrs, cols_ptrs, vals_ptrs, mask_ptrs,
        elocal.ctypes.data_as(_I32P),
        cursor.ctypes.data_as(_I32P), n_threads)
    return out
