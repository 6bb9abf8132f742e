"""ctypes bindings for the native IO library (optional accelerator).

``lib()`` returns the loaded library or None; callers keep pure-python
fallbacks. Build with ``make -C greptimedb_tpu/native`` (g++ only, no
external deps — see greptime_native.cpp).
"""

from __future__ import annotations

import ctypes
import json
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
        # a library built before the PromQL matrix encoder keeps the rest
        try:
            l.gt_json_matrix_bound.restype = ctypes.c_size_t
            l.gt_json_matrix_bound.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ]
            l.gt_json_matrix.restype = ctypes.c_size_t
            l.gt_json_matrix.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
                ctypes.c_char_p, ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_int64, ctypes.c_void_p,
            ]
        except AttributeError:
            l._gt_no_matrix = True
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


# stands in a reply's envelope where natively encoded bytes go; a client
# or a label cannot guess it
_SLOT = f"slot:{os.urandom(12).hex()}"


def json_around(body: dict, holder: dict, key: str, encoded) -> bytes | None:
    """``json.dumps(body)`` as bytes with ``encoded`` (what ``json_rows``
    or ``json_matrix`` wrote) in the place of ``holder[key]``, a dict
    inside ``body`` that is left as it was.  None where the body holds
    the slot's own text besides: the caller keeps ``json.dumps``."""
    kept, holder[key] = holder[key], _SLOT
    try:
        parts = json.dumps(body).split(f'"{_SLOT}"')
    finally:
        holder[key] = kept
    if len(parts) != 2:
        return None
    return b"".join((parts[0].encode(), encoded, parts[1].encode()))


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


def json_matrix(values, step_seconds, metrics: list[bytes]
                ) -> memoryview | None:
    """The ``result`` array of a Prometheus matrix reply from whole
    arrays: ``values`` float64 [S, T] (any row stride), ``step_seconds``
    float64 [T], ``metrics`` the JSON text of each series' ``metric``
    object.  The bytes ``json.dumps`` gives for the list of
    ``{"metric": ..., "values": [[t, repr(v)], ...]}`` (promql/format.py
    ``MatrixSeries.to_list``).  None when the library or the symbol is
    missing, or a step is not finite: the caller keeps ``json.dumps``."""
    l = lib()
    if l is None or getattr(l, "_gt_no_matrix", False):
        return None
    import numpy as np

    nseries, nsteps = values.shape
    if (values.dtype != np.float64 or len(metrics) != nseries
            or step_seconds.shape != (nsteps,)
            or not np.isfinite(step_seconds).all()):
        return None
    if (values.strides[1] != values.itemsize
            or values.strides[0] % values.itemsize):
        values = np.ascontiguousarray(values)
    steps = np.ascontiguousarray(step_seconds, dtype=np.float64)
    offsets = np.zeros(nseries + 1, np.int64)
    np.cumsum([len(m) for m in metrics], out=offsets[1:])
    out = np.empty(l.gt_json_matrix_bound(offsets.ctypes.data, nseries,
                                          nsteps), np.uint8)
    size = l.gt_json_matrix(
        values.ctypes.data, values.strides[0] // values.itemsize,
        steps.ctypes.data, b"".join(metrics), offsets.ctypes.data,
        nseries, nsteps, out.ctypes.data)
    return memoryview(out)[:size]
