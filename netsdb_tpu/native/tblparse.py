"""ctypes binding for the native .tbl parser (``native/tblparse.cpp``).

Columnar ingestion of TPC-H dbgen files — the C++ role of the
reference's ``tpchDataLoader.cc``, returning numpy columns instead of
per-row objects (the array form the TPU path wants). A failed build
raises ``NativeBuildError`` with the compiler's message; callers that
can do without the library ask :func:`available` first.
"""

from __future__ import annotations

import ctypes
from typing import Dict, List, Tuple

import numpy as np

_lib = None

_TYPE_CODES = {int: 0, float: 1, str: 2}


def _load():
    global _lib
    if _lib is not None:
        return _lib
    from netsdb_tpu.native.build import build_library

    lib = ctypes.CDLL(build_library("tblparse"))
    lib.tp_parse.restype = ctypes.c_void_p
    lib.tp_parse.argtypes = [ctypes.c_char_p, ctypes.c_int,
                             ctypes.POINTER(ctypes.c_int)]
    lib.tp_num_rows.restype = ctypes.c_int64
    lib.tp_num_rows.argtypes = [ctypes.c_void_p]
    lib.tp_error_msg.restype = ctypes.c_char_p
    lib.tp_error_msg.argtypes = [ctypes.c_void_p]
    lib.tp_int_col.restype = ctypes.POINTER(ctypes.c_int64)
    lib.tp_int_col.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.tp_float_col.restype = ctypes.POINTER(ctypes.c_double)
    lib.tp_float_col.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.tp_str_data.restype = ctypes.c_void_p
    lib.tp_str_data.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.tp_str_offsets.restype = ctypes.POINTER(ctypes.c_int64)
    lib.tp_str_offsets.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.tp_free.argtypes = [ctypes.c_void_p]
    _lib = lib
    return _lib


def available() -> bool:
    """False only when the toolchain cannot build the library."""
    from netsdb_tpu.native.build import NativeBuildError

    try:
        _load()
    except NativeBuildError:
        return False
    return True


def parse_columnar(path: str, schema: List[Tuple[str, type]]
                   ) -> Dict[str, np.ndarray]:
    """Parse a .tbl file into {column: array} (int64 / float64 /
    object-dtype strings). Raises ValueError on malformed input (same
    contract as the Python parser)."""
    lib = _load()
    types = (ctypes.c_int * len(schema))(
        *[_TYPE_CODES[t] for _, t in schema])
    h = lib.tp_parse(path.encode(), len(schema), types)
    if not h:
        raise FileNotFoundError(path)
    try:
        err = lib.tp_error_msg(h)
        if err:
            raise ValueError(f"{path}: {err.decode()}")
        n = lib.tp_num_rows(h)
        out: Dict[str, np.ndarray] = {}
        for i, (name, typ) in enumerate(schema):
            if typ is int:
                buf = np.ctypeslib.as_array(lib.tp_int_col(h, i), (n,))
                out[name] = buf.copy()
            elif typ is float:
                buf = np.ctypeslib.as_array(lib.tp_float_col(h, i), (n,))
                out[name] = buf.copy()
            else:
                offs = np.ctypeslib.as_array(lib.tp_str_offsets(h, i),
                                             (n + 1,)).copy()
                total = int(offs[-1])
                data_ptr = lib.tp_str_data(h, i)
                raw = ctypes.string_at(data_ptr, total) if total else b""
                ol = offs.tolist()
                col = np.empty(n, dtype=object)
                if raw.isascii():
                    # byte offsets == char offsets: decode once, slice
                    # (~2x faster than per-row bytes.decode)
                    blob = raw.decode()
                    col[:] = [blob[ol[j]:ol[j + 1]] for j in range(n)]
                else:
                    # multi-byte UTF-8: offsets are BYTE offsets, so
                    # slice bytes first, then decode each field
                    col[:] = [raw[ol[j]:ol[j + 1]].decode()
                              for j in range(n)]
                out[name] = col
        return out
    finally:
        lib.tp_free(h)
