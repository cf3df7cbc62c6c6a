"""ctypes bridge to the native data helpers (csrc/sample_idx.cpp).

The reference ships compiled dataset helpers for the index-building hot loop;
here a single C++ TU is compiled lazily with g++ (cached beside the source,
under a name made from the source's hash) and loaded via ctypes — no pybind11
dependency. Every entry point has a NumPy fallback so the package works
without a toolchain.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

import numpy as np

from ..utils.log import logger

__all__ = ["build_sample_idx", "native_available"]

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False

_CSRC = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), "csrc")


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        src = os.path.join(_CSRC, "sample_idx.cpp")
        try:
            # the binary is named by the source it was built from (git ignores
            # csrc/*.so): a stale .so carried along by a tree copy can never
            # be taken for a current one, whatever its mtime says
            with open(src, "rb") as f:
                digest = hashlib.sha256(f.read()).hexdigest()[:16]
            so = os.path.join(_CSRC, f"libpdnlp_data.{digest}.so")
            try:
                lib = ctypes.CDLL(so)
            except OSError:  # missing, or does not load here: build it
                tmp = f"{so}.build"
                subprocess.run(
                    ["g++", "-O3", "-shared", "-fPIC", "-o", tmp, src],
                    check=True,
                    capture_output=True,
                )
                os.replace(tmp, so)
                lib = ctypes.CDLL(so)
            lib.build_sample_idx.restype = ctypes.c_int
            lib.build_sample_idx.argtypes = [
                ctypes.POINTER(ctypes.c_int32),
                ctypes.POINTER(ctypes.c_int64),
                ctypes.c_int64,
                ctypes.c_int64,
                ctypes.c_int64,
                ctypes.POINTER(ctypes.c_int64),
            ]
            lib.build_blending_indices.restype = None
            lib.build_blending_indices.argtypes = [
                ctypes.POINTER(ctypes.c_double),
                ctypes.c_int64,
                ctypes.c_int64,
                ctypes.POINTER(ctypes.c_int32),
                ctypes.POINTER(ctypes.c_int64),
            ]
            _lib = lib
        except Exception as e:
            logger.warning(f"native data helpers unavailable ({e}); using numpy fallback")
            _lib = None
        return _lib


def native_available() -> bool:
    return _load() is not None


def build_sample_idx(sizes: np.ndarray, doc_idx: np.ndarray, seq_length: int, n_samples: int) -> np.ndarray:
    """[(doc_pos, doc_offset)] per sample boundary; shape [n_samples+1, 2]."""
    sizes = np.ascontiguousarray(sizes, dtype=np.int32)
    doc_idx = np.ascontiguousarray(doc_idx, dtype=np.int64)
    out = np.zeros((n_samples + 1, 2), dtype=np.int64)
    lib = _load()
    if lib is not None:
        rc = lib.build_sample_idx(
            sizes.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            doc_idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            len(doc_idx),
            seq_length,
            n_samples,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        )
        if rc != 0:
            raise ValueError("corpus exhausted before n_samples; increase epochs in doc_idx")
        return out
    return _build_sample_idx_np(sizes, doc_idx, seq_length, n_samples)


def build_blending_indices(weights: np.ndarray, n_samples: int):
    """Largest-deficit greedy blend assignment -> (dataset_index i32, sample_index i64)."""
    weights = np.ascontiguousarray(weights, dtype=np.float64)
    dataset_index = np.zeros(n_samples, dtype=np.int32)
    dataset_sample_index = np.zeros(n_samples, dtype=np.int64)
    lib = _load()
    if lib is not None:
        lib.build_blending_indices(
            weights.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            len(weights),
            n_samples,
            dataset_index.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            dataset_sample_index.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        )
        return dataset_index, dataset_sample_index
    counts = np.zeros(len(weights))
    for i in range(n_samples):
        d = int(np.argmax((i + 1) * weights - counts))
        dataset_index[i] = d
        dataset_sample_index[i] = counts[d]
        counts[d] += 1
    return dataset_index, dataset_sample_index


def _build_sample_idx_np(sizes, doc_idx, seq_length, n_samples):
    out = np.zeros((n_samples + 1, 2), dtype=np.int64)
    doc_pos, doc_offset = 0, 0
    for i in range(1, n_samples + 1):
        remaining = seq_length + 1
        while remaining > 0:
            if doc_pos >= len(doc_idx):
                raise ValueError("corpus exhausted before n_samples; increase epochs in doc_idx")
            doc_len = int(sizes[doc_idx[doc_pos]]) - doc_offset
            if doc_len > remaining:
                doc_offset += remaining
                remaining = 0
            else:
                remaining -= doc_len
                doc_pos += 1
                doc_offset = 0
        out[i] = (doc_pos, doc_offset)
    return out
