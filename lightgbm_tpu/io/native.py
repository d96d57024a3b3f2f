"""ctypes bridge to the native C++ data parser (cpp/parser.cpp).

Runs make on first use (a no-op when the shared library is current; the
reference ships its native code prebuilt in lib_lightgbm, ours builds
from source in-tree).
"""
from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Optional, Tuple

import numpy as np

from ..utils import log

_LIB: Optional[ctypes.CDLL] = None
_TRIED = False


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def _lib_path() -> str:
    return os.path.join(_repo_root(), "cpp", "libdataparser.so")


def _load() -> Optional[ctypes.CDLL]:
    global _LIB, _TRIED
    if _LIB is not None or _TRIED:
        return _LIB
    _TRIED = True
    path = _lib_path()
    try:
        # unconditional: make is a no-op when the library is newer than
        # parser.cpp, and a stale or missing one is rebuilt
        subprocess.run(["make", "-C", os.path.dirname(path)],
                       check=True, capture_output=True, timeout=120)
        lib = ctypes.CDLL(path)
    except (OSError, subprocess.SubprocessError) as e:
        log.warning("native parser unavailable (%s); file parsing uses "
                    "the Python parser", e)
        return None
    lib.parser_probe.restype = ctypes.c_int
    lib.parser_probe.argtypes = [
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_char), ctypes.POINTER(ctypes.c_int)]
    lib.parser_parse_delimited.restype = ctypes.c_int
    lib.parser_parse_delimited.argtypes = [
        ctypes.c_char_p, ctypes.c_char, ctypes.c_int, ctypes.c_int64,
        ctypes.c_int64, ctypes.POINTER(ctypes.c_double)]
    lib.parser_parse_libsvm.restype = ctypes.c_int
    lib.parser_parse_libsvm.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_double)]
    _LIB = lib
    return _LIB


def available() -> bool:
    return _load() is not None


def parse_file(path: str, label_column: int = 0):
    """Returns (X, y, query_boundaries|None) like io.parser.parse_file."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native parser unavailable")
    rows = ctypes.c_int64()
    cols = ctypes.c_int64()
    fmt = ctypes.c_int()
    delim = ctypes.c_char()
    header = ctypes.c_int()
    rc = lib.parser_probe(path.encode(), ctypes.byref(rows),
                          ctypes.byref(cols), ctypes.byref(fmt),
                          ctypes.byref(delim), ctypes.byref(header))
    if rc != 0:
        raise RuntimeError(f"parser_probe failed rc={rc}")
    r, c = rows.value, cols.value
    if fmt.value == 1:  # libsvm
        labels = np.empty(r, dtype=np.float64)
        x = np.empty((r, c), dtype=np.float64)
        rc = lib.parser_parse_libsvm(
            path.encode(), r, c,
            labels.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            x.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
        if rc != 0:
            raise RuntimeError(f"parser_parse_libsvm failed rc={rc}")
        return x, labels, None
    data = np.empty((r, c), dtype=np.float64)
    rc = lib.parser_parse_delimited(
        path.encode(), delim.value, header.value, r, c,
        data.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
    if rc != 0:
        raise RuntimeError(f"parser_parse_delimited failed rc={rc}")
    if c == 1:
        return data, None, None
    y = data[:, label_column].copy()
    x = np.delete(data, label_column, axis=1)
    return x, y, None
