"""ctypes bindings for the native IO library (optional accelerator).

``lib()`` returns the loaded library or None; callers keep pure-python
fallbacks. Build with ``make -C greptimedb_tpu/native`` (g++ only, no
external deps — see greptime_native.cpp).
"""

from __future__ import annotations

import ctypes
import os
import subprocess

_LIB = None
_TRIED = False

_DIR = os.path.dirname(__file__)
_SO = os.path.join(_DIR, "libgreptime_native.so")


class GtWalSpan(ctypes.Structure):
    _fields_ = [
        ("seq", ctypes.c_uint64),
        ("payload_off", ctypes.c_uint64),
        ("payload_len", ctypes.c_uint64),
    ]


class GtJsonCol(ctypes.Structure):
    _fields_ = [
        ("kind", ctypes.c_int32),
        ("data", ctypes.c_void_p),
        ("offsets", ctypes.c_void_p),
        ("valid", ctypes.c_void_p),
    ]


# GtJsonKind of greptime_native.cpp, by numpy dtype kind
_JSON_KIND = {"f": (0, "<f8"), "i": (1, "<i8"), "u": (2, "<u8"),
              "b": (3, "u1")}
_JSON_STR = 4


def build(quiet: bool = True) -> bool:
    """Compile the library in place; returns success."""
    try:
        r = subprocess.run(
            ["make", "-C", _DIR],
            capture_output=quiet, timeout=120,
        )
        return r.returncode == 0 and os.path.exists(_SO)
    except Exception:  # noqa: BLE001
        return False


def lib():
    global _LIB, _TRIED
    if _LIB is not None or _TRIED:
        return _LIB
    _TRIED = True
    if not os.path.exists(_SO):
        # never compile on a hot path (region open, request handling) —
        # the library is built by `make -C greptimedb_tpu/native` or an
        # explicit native.build() call
        return None
    try:
        l = ctypes.CDLL(_SO)
        l.gt_crc32.restype = ctypes.c_uint32
        l.gt_crc32.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
        l.gt_snappy_length.restype = ctypes.c_int64
        l.gt_snappy_length.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
        l.gt_snappy_decompress.restype = ctypes.c_int
        l.gt_snappy_decompress.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t,
            ctypes.c_char_p, ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_size_t),
        ]
        # v2 WAL frame scan (header-checksummed records); an older .so
        # without these symbols still serves crc32/snappy — the WAL
        # wrappers just return None and pure-python scanning takes over
        try:
            l.gt_wal_scan2.restype = ctypes.c_int64
            l.gt_wal_scan2.argtypes = [
                ctypes.c_char_p, ctypes.c_size_t, ctypes.c_uint64,
                ctypes.POINTER(GtWalSpan), ctypes.c_size_t,
                ctypes.POINTER(ctypes.c_size_t),
            ]
            l.gt_wal_find_boundary2.restype = ctypes.c_int64
            l.gt_wal_find_boundary2.argtypes = [
                ctypes.c_char_p, ctypes.c_size_t, ctypes.c_size_t,
            ]
        except AttributeError:
            l._gt_no_wal = True
        # a libstdc++ without floating-point to_chars leaves these out
        try:
            l.gt_json_rows_bound.restype = ctypes.c_size_t
            l.gt_json_rows_bound.argtypes = [
                ctypes.POINTER(GtJsonCol), ctypes.c_int32, ctypes.c_int64,
            ]
            l.gt_json_rows.restype = ctypes.c_size_t
            l.gt_json_rows.argtypes = [
                ctypes.POINTER(GtJsonCol), ctypes.c_int32, ctypes.c_int64,
                ctypes.c_void_p,
            ]
        except AttributeError:
            l._gt_no_json = True
        _LIB = l
    except OSError:
        _LIB = None
    return _LIB


# ---- typed wrappers (None-safe: callers check availability) ---------------

def crc32(data: bytes) -> int | None:
    l = lib()
    if l is None:
        return None
    return l.gt_crc32(data, len(data))


def snappy_decompress(data: bytes) -> bytes | None:
    l = lib()
    if l is None or not data:
        return None
    n = l.gt_snappy_length(data, len(data))
    if n < 0 or n > 1 << 31:
        raise ValueError("bad snappy header")
    out = ctypes.create_string_buffer(max(int(n), 1))
    out_len = ctypes.c_size_t(0)
    rc = l.gt_snappy_decompress(data, len(data), out, n, ctypes.byref(out_len))
    if rc != 0:
        raise ValueError(f"snappy decompress failed ({rc})")
    if out_len.value != n:
        raise ValueError(
            f"snappy length mismatch: got {out_len.value}, expected {n}"
        )
    return out.raw[: out_len.value]


def wal_scan(buf: bytes, min_seq: int) -> tuple[list[tuple[int, int, int]], int] | None:
    """Returns ([(seq, payload_off, payload_len)], good_end) or None."""
    l = lib()
    if l is None or getattr(l, "_gt_no_wal", False):
        return None
    cap = max(len(buf) // 20, 16)
    while True:
        spans = (GtWalSpan * cap)()
        good_end = ctypes.c_size_t(0)
        n = l.gt_wal_scan2(buf, len(buf), min_seq, spans, cap,
                           ctypes.byref(good_end))
        if n < 0:
            cap *= 2
            continue
        return (
            [(spans[i].seq, spans[i].payload_off, spans[i].payload_len)
             for i in range(n)],
            good_end.value,
        )


def wal_find_boundary(buf: bytes, start: int) -> int | None:
    """Next fully-valid record offset at/after ``start``; None when the
    damage reaches EOF, or when the native library is unavailable (the
    caller must fall back to the pure-python byte scan, NOT treat the
    miss as torn tail)."""
    l = lib()
    if l is None or getattr(l, "_gt_no_wal", False):
        return None
    off = l.gt_wal_find_boundary2(buf, len(buf), start)
    return None if off < 0 else int(off)


def json_rows(columns) -> memoryview | None:
    """The ``rows`` array of a /v1/sql reply, row-major, from whole numpy
    columns of one length: the bytes ``json.dumps`` gives for the same
    values as a list of lists (NaN as ``null``).  None when the library
    or the symbol is missing, there is no column, or a column is of a
    kind the encoder does not know (an object column holding anything
    but ``str`` and ``None`` included): the caller keeps ``json.dumps``."""
    l = lib()
    if l is None or getattr(l, "_gt_no_json", False) or not columns:
        return None
    import numpy as np

    n = len(columns[0])
    cols = (GtJsonCol * len(columns))()
    held = []  # what the pointers point into
    for spec, col in zip(cols, columns):
        kind = col.dtype.kind
        if kind in _JSON_KIND and col.dtype.itemsize <= 8:
            spec.kind, dtype = _JSON_KIND[kind]
            data = np.ascontiguousarray(col, dtype=dtype)
            spec.data = data.ctypes.data
        elif kind in "OU":
            import pyarrow as pa

            try:
                data = pa.array(col)
                if pa.types.is_null(data.type):
                    data = data.cast(pa.string())
            except (pa.ArrowException, TypeError, ValueError):
                return None  # not text: a lone surrogate, a number
            if not (isinstance(data, pa.Array)
                    and pa.types.is_string(data.type) and data.offset == 0):
                return None
            valid, offsets, text = data.buffers()
            spec.kind = _JSON_STR
            spec.offsets = offsets.address
            spec.data = text.address if text is not None else None
            spec.valid = valid.address if valid is not None else None
        else:
            return None
        held.append(data)
    out = np.empty(l.gt_json_rows_bound(cols, len(columns), n), np.uint8)
    size = l.gt_json_rows(cols, len(columns), n, out.ctypes.data)
    return memoryview(out)[:size]
