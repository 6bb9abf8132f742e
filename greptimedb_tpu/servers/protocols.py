"""Wire-format codecs: InfluxDB line protocol, Prometheus remote write.

- Line protocol (reference src/servers/src/influxdb.rs):
  ``measurement[,tag=v...] field=value[,field2=v2...] [timestamp]``.
- Remote write (reference src/servers/src/prom_store.rs + prom_row_builder):
  snappy-compressed protobuf WriteRequest; parsed here with a minimal
  hand-rolled proto wire reader (no generated classes in the image).

Each metric-ingest format has TWO decoders:

- a **vectorized** one (default) that produces columnar batches directly —
  NumPy value arrays plus dictionary-mapped int32 tag codes
  (``datatypes.batch.DictColumn``, the PR 5 ``__tagcode_*__`` trick in
  reverse) with zero per-row Python dicts/tuples on the hot path.  Line
  protocol lowers to one C-level byte transform plus a pyarrow CSV parse
  (multithreaded number parsing); remote write keeps the per-TIMESERIES
  protobuf walk but assembles columns by ``np.repeat`` over per-series
  label sets instead of a per-row Python loop.
- the original **row-at-a-time** decoder (``*_legacy``): the fallback
  for wire shapes the vectorized line-protocol parser does not cover
  (escapes, quoted string fields, ragged per-line schemas) and the
  parity oracle of tests/test_ingest_pipeline.py.  Rows decoded through
  it count into ``greptime_ingest_object_decode_rows_total`` — the
  vectorized hot path pins that counter at 0.
"""

from __future__ import annotations

import math
import time
from collections import defaultdict

from greptimedb_tpu.errors import InvalidArguments
from greptimedb_tpu.utils import telemetry
from greptimedb_tpu.utils.tracing import TRACER

M_OBJECT_DECODE_ROWS = telemetry.REGISTRY.counter(
    "greptime_ingest_object_decode_rows_total",
    "Rows decoded through the per-row object path (legacy/fallback); "
    "the vectorized wire parsers keep this at 0",
    labels=("protocol",))
M_PARSE_SECONDS = telemetry.REGISTRY.histogram(
    "greptime_ingest_parse_seconds",
    "Wire-format decode latency per ingest batch", labels=("protocol",),
    buckets=(0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0))
M_INGEST_BATCHES = telemetry.REGISTRY.counter(
    "greptime_ingest_batches_total",
    "Wire ingest batches decoded", labels=("protocol", "path"))


_PA_TUNED = False


def _tune_pyarrow() -> None:
    """One-time pyarrow knob for the ingest hot path: on Python 3.10,
    every blocking pyarrow call (``read_csv``, flight reads, ...)
    constructs a SignalStopHandler whose bpo-42248 workaround walks the
    ENTIRE gc heap (``gc.get_referrers``) — a fixed ~10-15 ms tax per
    call once jax is resident, dwarfing a wire batch's actual decode.
    The workaround only matters when a read is cancelled by a signal
    (a traceback refcycle may then linger until the next gc pass), so
    trading it away on the steady-state server path is free."""
    global _PA_TUNED
    if not _PA_TUNED:
        import pyarrow.lib as palib

        palib.have_signal_refcycle = False
        _PA_TUNED = True


class _Unvectorizable(Exception):
    """Internal: this body needs the row-at-a-time decoder (escapes,
    quoted strings, ragged schemas, malformed lines that deserve the
    legacy parser's per-line error messages)."""


# ---------------------------------------------------------------------------
# InfluxDB line protocol
# ---------------------------------------------------------------------------

def _split_unescaped(s: str, sep: str, quotes: bool = False) -> list[str]:
    """Split on unescaped sep; with quotes=True, separators inside
    double-quoted strings are literal (field-section semantics)."""
    out = []
    buf = []
    i = 0
    in_quote = False
    while i < len(s):
        c = s[i]
        if c == "\\" and i + 1 < len(s):
            buf.append(s[i:i + 2])
            i += 2
            continue
        if quotes and c == '"':
            in_quote = not in_quote
            buf.append(c)
            i += 1
            continue
        if c == sep and not in_quote:
            out.append("".join(buf))
            buf = []
        else:
            buf.append(c)
        i += 1
    out.append("".join(buf))
    return out


def _split_sections(line: str) -> list[str]:
    """Split a line-protocol line into measurement+tags / fields / ts,
    honoring escapes everywhere and quotes in the field section."""
    # section 1: no quote special-casing
    first = _split_unescaped(line, " ")
    head = first[0]
    rest = " ".join(first[1:])
    if not rest:
        return [head]
    tail = _split_unescaped(rest, " ", quotes=True)
    tail = [t for t in tail if t != ""]
    if len(tail) == 1:
        return [head, tail[0]]
    return [head, tail[0], " ".join(tail[1:])]


def _unescape(s: str) -> str:
    return (
        s.replace("\\,", ",").replace("\\ ", " ").replace("\\=", "=")
        .replace('\\"', '"').replace("\\\\", "\\")
    )


def _parse_field_value(raw: str):
    if raw.startswith('"') and raw.endswith('"') and len(raw) >= 2:
        return _unescape(raw[1:-1])
    if raw.endswith("i"):
        return int(raw[:-1])
    if raw.endswith("u"):
        return int(raw[:-1])
    low = raw.lower()
    if low in ("t", "true"):
        return True
    if low in ("f", "false"):
        return False
    return float(raw)


_PRECISION_DIV = {"ns": 1_000_000, "us": 1_000, "ms": 1, "s": 0.001}


def parse_line_protocol(
    body: "str | bytes", precision: str = "ns"
) -> dict[str, dict[str, list]]:
    """Parse line protocol into per-measurement columnar dicts.

    Returns {measurement: {tag/field/ts column -> values}}; missing
    tags/fields across lines are None-filled (schema union per table).
    Timestamps normalize to epoch ms.  From the vectorized path columns
    come back as NumPy arrays / ``DictColumn`` tag codes; the legacy
    fallback returns Python lists — both feed
    ``Region.write`` to identical table contents (pinned in
    tests/test_ingest_pipeline.py).
    """
    div = _PRECISION_DIV.get(precision)
    if div is None:
        raise InvalidArguments(f"bad precision {precision}")
    with M_PARSE_SECONDS.labels("influxdb").time(), \
            TRACER.stage("ingest_parse", protocol="influxdb"):
        raw = body.encode("utf-8") if isinstance(body, str) else body
        try:
            out = _parse_line_protocol_vec(raw, div)
            M_INGEST_BATCHES.labels("influxdb", "vectorized").inc()
            return out
        except _Unvectorizable:
            pass  # row-at-a-time fallback below
        text = body.decode("utf-8") if isinstance(body, bytes) else body
        out = parse_line_protocol_legacy(text, precision)
        M_INGEST_BATCHES.labels("influxdb", "legacy").inc()
        M_OBJECT_DECODE_ROWS.labels("influxdb").inc(
            sum(len(t["ts"]) for t in out.values()))
        return out


def _lp_const_col(col, n: int) -> "bytes | None":
    """The column's single repeated value when every row is byte-identical
    (offset stride + one memcmp against value*n — no per-row objects),
    else None.  Used to verify the uniform-schema precondition: key and
    section-sentinel columns of a well-formed batch are constant."""
    import numpy as np
    import pyarrow as pa

    if col.null_count:
        return None
    if col.type == pa.string():
        odt = np.int32
    elif col.type == pa.large_string():
        odt = np.int64
    else:
        return None
    bufs = col.buffers()
    off = np.frombuffer(bufs[1], dtype=odt, count=n + 1)
    start, end = int(off[0]), int(off[n])
    if (end - start) % n:
        return None
    w = (end - start) // n
    if w and not (np.diff(off) == w).all():
        return None
    if w == 0:
        return b""
    data = bufs[2].to_pybytes()[start:end]
    first = data[:w]
    return first if data == first * n else None


def _lp_dict_column(col):
    """Arrow string column → DictColumn (C-level hash over the column;
    per-row output is int32 codes, vocabulary is the only object array)."""
    import numpy as np

    from greptimedb_tpu.datatypes.batch import DictColumn

    d = col.dictionary_encode()
    return DictColumn(
        np.asarray(d.dictionary.to_pylist(), dtype=object),
        d.indices.to_numpy(),
    )


def _parse_line_protocol_vec(raw: bytes, div) -> dict:  # gl: warm-path(host)
    """Vectorized line-protocol decode for uniform-schema batches.

    The trick: with no escapes and no quoted strings, ``=``, ``,`` and the
    section space are unambiguous token separators — so two C-level
    ``bytes.replace`` passes turn the whole body into a CSV (spaces become
    a ``\\x01`` sentinel COLUMN marking the tags/fields/timestamp section
    boundaries) and pyarrow's multithreaded CSV reader does all per-row
    work: tokenization, number parsing, null detection.  Post-passes are
    O(columns): key columns must be constant (verified by one memcmp
    each), tag values dictionary-encode to int32 codes, field columns are
    already numeric arrays.  Anything else —  ragged schemas, quoted
    strings, comments, malformed lines — raises ``_Unvectorizable`` and
    the row-at-a-time parser (with its per-line error messages) takes
    over.
    """
    import io

    import numpy as np
    import pyarrow as pa
    import pyarrow.csv as pacsv

    _tune_pyarrow()
    if b"\\" in raw or b'"' in raw or b"\x01" in raw:
        raise _Unvectorizable("escapes/quoted strings")
    body = raw.strip()
    if not body:
        return {}
    if (body.startswith(b"#") or b"\n#" in body or b"\n\n" in body
            or b"\r" in body or b"\n " in body or b" \n" in body):
        # comment/blank lines, CR breaks, per-line whitespace: shapes that
        # need per-line filtering
        raise _Unvectorizable("needs line filtering")
    # trailing newline: the CSV reader cannot infer columns without one
    data = body.replace(b"=", b",").replace(b" ", b",\x01,") + b"\n"
    ragged = []
    try:
        # eager multithreaded reader (the SignalStopHandler gc-walk it
        # wraps each call in is disarmed by _tune_pyarrow): 1MB blocks
        # split a multi-MB body across cores — tokenization and float
        # conversion are the dominant decode cost
        table = pacsv.read_csv(
            io.BytesIO(data),
            read_options=pacsv.ReadOptions(
                autogenerate_column_names=True, block_size=1 << 20),
            parse_options=pacsv.ParseOptions(
                delimiter=",", quote_char=False,
                invalid_row_handler=lambda row: ragged.append(1) or "skip"),
            # no null spellings: "nan"/"inf" must parse as floats (legacy
            # float() semantics) and "" must surface as a conversion
            # failure, not a silent null
            convert_options=pacsv.ConvertOptions(null_values=[]),
        )
    except pa.ArrowInvalid as e:
        raise _Unvectorizable(str(e)) from None
    if ragged:
        raise _Unvectorizable("ragged line shapes")
    table = table.combine_chunks()
    n = table.num_rows
    k = table.num_columns
    if n == 0 or k < 3:
        raise _Unvectorizable("degenerate shape")
    cols = [table.column(i).chunk(0) for i in range(k)]
    if any(c.null_count for c in cols):
        raise _Unvectorizable("empty tokens")

    # section boundaries: the constant "\x01" sentinel columns
    sentinels = [
        i for i, c in enumerate(cols)
        if pa.types.is_string(c.type) and c[0].as_py() == "\x01"
        and _lp_const_col(c, n) == b"\x01"
    ]
    if len(sentinels) == 1:
        s1, ts_idx = sentinels[0], None
        field_end = k
    elif len(sentinels) == 2 and sentinels[1] == k - 2:
        s1, ts_idx = sentinels[0], k - 1
        field_end = k - 2
    else:
        raise _Unvectorizable("bad section structure")
    if (s1 - 1) % 2 or (field_end - s1 - 1) % 2 or field_end == s1 + 1:
        raise _Unvectorizable("unpaired key/value tokens")

    def const_key(i: int) -> str:
        key = _lp_const_col(cols[i], n)
        if key is None:
            raise _Unvectorizable(f"varying key at column {i}")
        return key.decode("utf-8")

    # tag section: (key, DictColumn) pairs — values become int32 codes
    # over a tiny vocabulary, never per-row string objects
    tags: list[tuple[str, object]] = []
    for i in range(1, s1, 2):
        if not pa.types.is_string(cols[i + 1].type):
            raise _Unvectorizable("non-string tag value column")
        tags.append((const_key(i), _lp_dict_column(cols[i + 1])))

    # field section: numeric columns are ready; string columns may be
    # uniformly i/u-suffixed integers or booleans (column-level checks,
    # C-level regex) — anything mixed goes to the legacy parser
    import pyarrow.compute as pc

    fields: list[tuple[str, np.ndarray]] = []
    for i in range(s1 + 1, field_end, 2):
        key = const_key(i)
        vc = cols[i + 1]
        if pa.types.is_floating(vc.type):
            vals = vc.to_numpy()
        elif pa.types.is_integer(vc.type):
            # unsuffixed numbers are floats in line protocol
            vals = vc.to_numpy().astype(np.float64)
        elif pa.types.is_string(vc.type):
            if bool(pc.all(pc.match_substring_regex(
                    vc, r"^-?[0-9]+[iu]$")).as_py()):
                try:
                    vals = pc.cast(
                        pc.utf8_replace_slice(vc, start=-1, stop=1 << 30,
                                              replacement=""),
                        pa.int64()).to_numpy()
                except pa.ArrowInvalid:
                    raise _Unvectorizable("int overflow") from None
            elif bool(pc.all(pc.is_in(
                    pc.ascii_lower(vc),
                    value_set=pa.array(["t", "true", "f", "false"]))
                    ).as_py()):
                vals = pc.is_in(
                    pc.ascii_lower(vc),
                    value_set=pa.array(["t", "true"])).to_numpy(
                        zero_copy_only=False)
            else:
                raise _Unvectorizable("mixed/string field values")
        else:
            raise _Unvectorizable(f"field column type {vc.type}")
        fields.append((key, vals))

    # timestamps: already int64 from the CSV reader, normalized to ms
    if ts_idx is not None:
        tc = cols[ts_idx]
        if not pa.types.is_integer(tc.type):
            raise _Unvectorizable("non-integer timestamps")
        ts_raw = tc.to_numpy().astype(np.int64)
        if div >= 1:
            ts_ms = ts_raw // div
        else:
            if len(ts_raw) and int(np.abs(ts_raw).max()) > (1 << 62) // 1000:
                raise _Unvectorizable("timestamp overflow")
            ts_ms = ts_raw * 1000
    else:
        ts_ms = np.full(n, int(time.time() * 1000), dtype=np.int64)

    # measurement routing: dictionary codes once, then per-table slices
    mcol = cols[0]
    if not pa.types.is_string(mcol.type):
        raise _Unvectorizable("non-string measurement")
    md = mcol.dictionary_encode()
    mvals = md.dictionary.to_pylist()
    if any(not m for m in mvals):
        raise _Unvectorizable("empty measurement")
    mcodes = md.indices.to_numpy()
    out: dict[str, dict] = {}
    for mi, measurement in enumerate(mvals):
        sel = None if len(mvals) == 1 else np.nonzero(mcodes == mi)[0]
        tcols: dict[str, object] = {}
        for key, dc in tags:
            tcols[key] = dc if sel is None else dc.take(sel)
        fcols: dict[str, np.ndarray] = {}
        for key, vals in fields:
            fcols[key] = vals if sel is None else vals[sel]
        # legacy column order (tags, fields, ts) so name collisions — a
        # tag or field literally named "ts" — shadow identically
        tbl: dict[str, object] = {}
        for key in sorted(tcols):
            tbl[key] = tcols[key]
        for key in sorted(fcols):
            tbl[key] = fcols[key]
        tbl["ts"] = ts_ms if sel is None else ts_ms[sel]
        out[measurement] = {
            "__tags__": sorted(tcols), "__fields__": sorted(fcols), **tbl,
        }
    return out


def parse_line_protocol_legacy(
    body: str, precision: str = "ns"
) -> dict[str, dict[str, list]]:
    """Row-at-a-time reference decoder (the seed path): per-line splits,
    per-row dict/tuple assembly.  Kept as the parity oracle and the
    fallback for wire shapes outside the vectorized surface."""
    div = _PRECISION_DIV.get(precision)
    if div is None:
        raise InvalidArguments(f"bad precision {precision}")
    per_table: dict[str, list[tuple[dict, dict, int]]] = defaultdict(list)
    now_ms = int(time.time() * 1000)
    for lineno, line in enumerate(body.splitlines(), 1):
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        # measurement+tags SPACE fields SPACE [ts]
        parts = _split_sections(line)
        if len(parts) < 2 or not parts[1]:
            raise InvalidArguments(f"line {lineno}: need fields: {line!r}")
        head = _split_unescaped(parts[0], ",")
        measurement = _unescape(head[0])
        if not measurement:
            raise InvalidArguments(f"line {lineno}: empty measurement")
        tags = {}
        for t in head[1:]:
            kv = _split_unescaped(t, "=")
            if len(kv) != 2:
                raise InvalidArguments(f"line {lineno}: bad tag {t!r}")
            tags[_unescape(kv[0])] = _unescape(kv[1])
        fields = {}
        for f in _split_unescaped(parts[1], ",", quotes=True):
            kv = _split_unescaped(f, "=", quotes=True)
            if len(kv) != 2:
                raise InvalidArguments(f"line {lineno}: bad field {f!r}")
            try:
                fields[_unescape(kv[0])] = _parse_field_value(kv[1])
            except ValueError:
                raise InvalidArguments(
                    f"line {lineno}: bad field value {kv[1]!r}"
                ) from None
        if not fields:
            raise InvalidArguments(f"line {lineno}: no fields")
        if len(parts) >= 3:
            try:
                ts_raw = int(parts[2])
            except ValueError:
                raise InvalidArguments(
                    f"line {lineno}: bad timestamp {parts[2]!r}"
                ) from None
            # integer floor division: float math corrupts epoch-ns > 2^53
            ts_ms = ts_raw // div if div >= 1 else ts_raw * 1000
        else:
            ts_ms = now_ms
        per_table[measurement].append((tags, fields, ts_ms))

    out: dict[str, dict[str, list]] = {}
    for table, rows in per_table.items():
        tag_names = sorted({k for tags, _f, _t in rows for k in tags})
        field_names = sorted({k for _t, fields, _ in rows for k in fields})
        cols: dict[str, list] = {k: [] for k in tag_names}
        cols.update({k: [] for k in field_names})
        cols["ts"] = []
        for tags, fields, ts_ms in rows:
            for k in tag_names:
                cols[k].append(tags.get(k))
            for k in field_names:
                cols[k].append(fields.get(k))
            cols["ts"].append(ts_ms)
        out[table] = {"__tags__": tag_names, "__fields__": field_names, **cols}
    return out


# ---------------------------------------------------------------------------
# Prometheus remote write: minimal protobuf wire parsing
# ---------------------------------------------------------------------------

def _pb_fields(data: bytes):
    """Yield (field_number, wire_type, value_bytes_or_int) from a message."""
    pos = 0
    n = len(data)
    while pos < n:
        key = 0
        shift = 0
        while True:
            b = data[pos]
            pos += 1
            key |= (b & 0x7F) << shift
            if not (b & 0x80):
                break
            shift += 7
        field, wtype = key >> 3, key & 0x07
        if wtype == 0:  # varint
            v = 0
            shift = 0
            while True:
                b = data[pos]
                pos += 1
                v |= (b & 0x7F) << shift
                if not (b & 0x80):
                    break
                shift += 7
            yield field, wtype, v
        elif wtype == 1:  # 64-bit
            yield field, wtype, data[pos:pos + 8]
            pos += 8
        elif wtype == 2:  # length-delimited
            ln = 0
            shift = 0
            while True:
                b = data[pos]
                pos += 1
                ln |= (b & 0x7F) << shift
                if not (b & 0x80):
                    break
                shift += 7
            yield field, wtype, data[pos:pos + ln]
            pos += ln
        elif wtype == 5:  # 32-bit
            yield field, wtype, data[pos:pos + 4]
            pos += 4
        else:
            raise InvalidArguments(f"unsupported protobuf wire type {wtype}")


def _zigzag_or_signed(v: int) -> int:
    """Interpret a varint as a signed int64 (two's complement)."""
    if v >= 1 << 63:
        v -= 1 << 64
    return v


def parse_remote_write(body: bytes) -> dict[str, dict[str, list]]:
    """Parse a prometheus.WriteRequest into per-metric columnar dicts.

    WriteRequest{ timeseries=1: TimeSeries{ labels=1: Label{name=1,value=2},
    samples=2: Sample{value=1(double), timestamp=2(int64)} } }.
    The __name__ label routes to a table; remaining labels are tags; the
    sample value lands in column 'val' (greptime's metric data model).
    """
    with M_PARSE_SECONDS.labels("prom_remote_write").time(), \
            TRACER.stage("ingest_parse", protocol="prom_remote_write"):
        out = _parse_remote_write_vec(body)
        M_INGEST_BATCHES.labels("prom_remote_write", "vectorized").inc()
        return out


def _walk_write_request(body: bytes):
    """Yield (labels, values_list, ts_list) per TimeSeries — the protobuf
    walk shared by both decoders.  Label decode is per SERIES (protobuf
    forces that); sample payloads append into flat Python-float/int lists
    converted to arrays in one C pass by the caller."""
    import struct

    unpack_d = struct.Struct("<d").unpack
    for field, _wt, ts_bytes in _pb_fields(body):
        if field != 1:
            continue
        labels: dict[str, str] = {}
        vals: list[float] = []
        tss: list[int] = []
        for f2, _wt2, v2 in _pb_fields(ts_bytes):
            if f2 == 1:  # Label
                name = value = ""
                for f3, _wt3, v3 in _pb_fields(v2):
                    if f3 == 1:
                        name = v3.decode("utf-8")
                    elif f3 == 2:
                        value = v3.decode("utf-8")
                labels[name] = value
            elif f2 == 2:  # Sample
                val = math.nan
                ts = 0
                for f3, wt3, v3 in _pb_fields(v2):
                    if f3 == 1:
                        val = unpack_d(v3)[0]
                    elif f3 == 2:
                        ts = _zigzag_or_signed(v3)
                vals.append(val)
                tss.append(ts)
        yield labels, vals, tss


def _parse_remote_write_vec(body: bytes) -> dict:  # gl: warm-path(host)
    """Columnar WriteRequest assembly: per-series label sets factorize to
    a vocabulary + counts, tag columns come out as ``DictColumn`` via one
    ``np.repeat`` per tag (C-level), values/timestamps as single
    ``np.asarray`` conversions — no per-ROW Python loop anywhere."""
    import numpy as np
    import pandas as pd

    from greptimedb_tpu.datatypes.batch import DictColumn

    # per table: parallel per-series lists
    acc: dict[str, tuple[list, list, list, list]] = {}
    for labels, vals, tss in _walk_write_request(body):
        metric = labels.pop("__name__", "")
        if not metric or not vals:
            continue
        a = acc.get(metric)
        if a is None:
            a = acc[metric] = ([], [], [], [])
        tag_sets, counts, flat_vals, flat_tss = a
        tag_sets.append(labels)
        counts.append(len(vals))
        flat_vals.extend(vals)
        flat_tss.extend(tss)

    out: dict[str, dict] = {}
    for table, (tag_sets, counts, flat_vals, flat_tss) in acc.items():
        tag_names = sorted({k for tags in tag_sets for k in tags})
        counts_np = np.asarray(counts, dtype=np.int64)
        cols: dict[str, object] = {}
        for k in tag_names:
            per_series = np.asarray(
                [tags.get(k, "") for tags in tag_sets], dtype=object)
            codes, uniq = pd.factorize(per_series)
            cols[k] = DictColumn(
                np.asarray(uniq, dtype=object),
                np.repeat(codes.astype(np.int32), counts_np),
            )
        cols["ts"] = np.asarray(flat_tss, dtype=np.int64)
        cols["val"] = np.asarray(flat_vals, dtype=np.float64)
        out[table] = {"__tags__": tag_names, "__fields__": ["val"], **cols}
    return out


def parse_remote_write_legacy(body: bytes) -> dict[str, dict[str, list]]:
    """Row-at-a-time WriteRequest decoder (the seed path, for A/B and
    parity): per-row tuples, per-row × per-tag Python list assembly."""
    per_table: dict[str, list[tuple[dict, float, int]]] = defaultdict(list)
    for labels, vals, tss in _walk_write_request(body):
        metric = labels.pop("__name__", "")
        if not metric:
            continue
        for val, ts in zip(vals, tss):
            per_table[metric].append((labels, val, ts))

    out: dict[str, dict[str, list]] = {}
    for table, rows in per_table.items():
        tag_names = sorted({k for tags, _v, _t in rows for k in tags})
        cols: dict[str, list] = {k: [] for k in tag_names}
        cols["ts"] = []
        cols["val"] = []
        for tags, val, ts in rows:
            for k in tag_names:
                cols[k].append(tags.get(k, ""))
            cols["ts"].append(ts)
            cols["val"].append(val)
        out[table] = {"__tags__": tag_names, "__fields__": ["val"], **cols}
    return out


# ---------------------------------------------------------------------------
# Arrow IPC bulk insert (the standalone HTTP surface of the in-cluster
# Flight do_put plane — reference gRPC bulk inserts / BulkInsertService)
# ---------------------------------------------------------------------------

def parse_arrow_bulk(body: bytes) -> dict:  # gl: warm-path(host)
    """Arrow IPC stream → one columnar write batch for ``_ingest_columns``.

    The highest-rate wire format: the client ships columns, so decode is
    structural — string/dictionary columns classify as tags (passed
    through as ``DictColumn`` codes+vocabulary, or dictionary-encoded at
    C level), every other non-``ts`` column as a field (zero-copy NumPy
    view where the buffer layout allows).  ``ts`` is required: int64
    epoch milliseconds or any Arrow timestamp type (converted to ms).
    Null-free columns never materialize a per-row Python object; a
    column WITH nulls drops to the object path (None must survive to the
    region's NULL semantics) and is counted in
    ``greptime_ingest_object_decode_rows_total{protocol="arrow"}``."""
    import numpy as np
    import pyarrow as pa

    with M_PARSE_SECONDS.labels("arrow").time(), \
            TRACER.stage("ingest_parse", protocol="arrow"):
        _tune_pyarrow()
        try:
            with pa.ipc.open_stream(pa.py_buffer(body)) as r:
                table = r.read_all()
        except (pa.ArrowInvalid, pa.ArrowIOError) as e:
            raise InvalidArguments(f"bad arrow ipc stream: {e}") from None
        if "ts" not in table.column_names:
            raise InvalidArguments("arrow bulk batch needs a 'ts' column")
        n = table.num_rows
        objdec = False
        ts_int = False
        tag_names: list[str] = []
        field_names: list[str] = []
        cols: dict[str, object] = {}
        for name in table.column_names:
            col = table.column(name).combine_chunks()
            is_ts = name == "ts"
            stringish = (pa.types.is_dictionary(col.type)
                         or pa.types.is_string(col.type)
                         or pa.types.is_large_string(col.type))
            if not is_ts:
                (tag_names if stringish else field_names).append(name)
            if is_ts:
                if col.null_count:
                    # surface the NOT NULL violation here — downstream
                    # astype would turn None into an opaque 500
                    raise InvalidArguments("arrow bulk 'ts' contains nulls")
                # ts converts structurally — a timestamp-typed column
                # would otherwise decode to datetime objects the region
                # cannot take
                ts_int = pa.types.is_integer(col.type)
                cols[name] = _arrow_ts_ms(col)
            elif col.null_count:
                # object path: per-row PyObjects (None survives to the
                # region's NULL semantics, including the NOT NULL error
                # for a null ts)
                objdec = True
                cols[name] = col.to_pylist()
            elif stringish:
                # dictionary-coded on the wire passes straight through as
                # codes + vocabulary; plain strings dictionary-encode at
                # C level — either way no per-row decode.  None = a null
                # vocabulary entry: NULL must survive → object path
                from greptimedb_tpu.datatypes.batch import DictColumn

                dc = DictColumn.from_arrow(col)
                if dc is None:
                    objdec = True
                    cols[name] = col.to_pylist()
                else:
                    cols[name] = dc
            else:
                cols[name] = col.to_numpy(zero_copy_only=False)
        if objdec:
            M_OBJECT_DECODE_ROWS.labels("arrow").inc(n)
        M_INGEST_BATCHES.labels("arrow", "vectorized").inc()
        cols["__tags__"] = sorted(tag_names)
        cols["__fields__"] = sorted(field_names)
        if not objdec and ts_int and n:
            # every column decoded structurally and ts is already int64
            # epoch ms on the wire: the body IS a valid slim WAL payload
            # (replay_wal re-derives codes/tsids from exactly these
            # columns), so the region can log the wire bytes verbatim
            # instead of re-serializing the batch — dropped downstream
            # when the batch is sliced across regions or a schema column
            # is missing (region.py validates before using it)
            cols["__wire_ipc__"] = body
        return cols


def _arrow_ts_ms(col):
    """Arrow ts column → int64 epoch ms (zero-copy for int64 input)."""
    import numpy as np
    import pyarrow as pa

    if pa.types.is_timestamp(col.type):
        return (col.to_numpy(zero_copy_only=False)
                .astype("datetime64[ms]").astype(np.int64))
    if pa.types.is_integer(col.type):
        return col.to_numpy(zero_copy_only=False).astype(np.int64,
                                                         copy=False)
    raise InvalidArguments(f"arrow bulk 'ts' must be int64 ms or a "
                           f"timestamp type, got {col.type}")


# ---------------------------------------------------------------------------
# Loki protobuf push (snappy logproto.PushRequest)
# ---------------------------------------------------------------------------

def _parse_loki_labels(s: str) -> dict[str, str]:
    """`{job="api", env="prod"}` → dict (Loki's label-set string form)."""
    out: dict[str, str] = {}
    s = s.strip()
    if s.startswith("{"):
        s = s[1:]
    if s.endswith("}"):
        s = s[:-1]
    i, n = 0, len(s)
    while i < n:
        while i < n and s[i] in ", \t":
            i += 1
        j = i
        while j < n and s[j] not in "=":
            j += 1
        name = s[i:j].strip()
        i = j + 1
        if i < n and s[i] == '"':
            i += 1
            val = []
            while i < n and s[i] != '"':
                if s[i] == "\\" and i + 1 < n:
                    i += 1
                val.append(s[i])
                i += 1
            i += 1  # closing quote
            if name:
                out[name] = "".join(val)
        else:  # unquoted (not produced by real clients; be lenient)
            j = i
            while j < n and s[j] not in ",}":
                j += 1
            if name:
                out[name] = s[i:j].strip()
            i = j
    return out


def parse_loki_push(body: bytes) -> list[tuple[dict, str, int]]:
    """logproto.PushRequest → [(labels, line, ts_ms)].

    PushRequest{ streams=1: StreamAdapter{ labels=1 (label-set string),
    entries=2: EntryAdapter{ timestamp=1 (Timestamp{seconds=1,nanos=2}),
    line=2 } } } — the snappy layer is the caller's concern.
    """
    rows: list[tuple[dict, str, int]] = []
    for field, _wt, stream_bytes in _pb_fields(body):
        if field != 1:
            continue
        labels: dict[str, str] = {}
        entries: list[tuple[int, str]] = []
        for f2, _wt2, v2 in _pb_fields(stream_bytes):
            if f2 == 1:  # labels string
                labels = _parse_loki_labels(v2.decode("utf-8", "replace"))
            elif f2 == 2:  # EntryAdapter
                secs = nanos = 0
                line = ""
                for f3, _wt3, v3 in _pb_fields(v2):
                    if f3 == 1:  # Timestamp
                        for f4, _wt4, v4 in _pb_fields(v3):
                            if f4 == 1:
                                secs = _zigzag_or_signed(v4)
                            elif f4 == 2:
                                nanos = _zigzag_or_signed(v4)
                    elif f3 == 2:
                        line = v3.decode("utf-8", "replace")
                entries.append((secs * 1000 + nanos // 1_000_000, line))
        for ts_ms, line in entries:
            rows.append((labels, line, ts_ms))
    return rows


# ---------------------------------------------------------------------------
# Prometheus remote read (snappy prometheus.ReadRequest/ReadResponse)
# Reference: src/servers/src/http/prom_store.rs + src/servers/src/prom_store.rs
# ---------------------------------------------------------------------------

# LabelMatcher.Type enum (remote.proto): EQ=0, NEQ=1, RE=2, NRE=3
_READ_MATCHER_OPS = {0: "=", 1: "!=", 2: "=~", 3: "!~"}


def parse_remote_read(body: bytes) -> list[dict]:
    """prometheus.ReadRequest → [{start_ms, end_ms,
    matchers: [(op, name, value)]}] (hints are advisory; ignored)."""
    queries: list[dict] = []
    for f, _wt, qb in _pb_fields(body):
        if f != 1:  # queries
            continue
        q = {"start_ms": 0, "end_ms": 0, "matchers": []}
        for f2, _wt2, v2 in _pb_fields(qb):
            if f2 == 1:
                q["start_ms"] = _zigzag_or_signed(v2)
            elif f2 == 2:
                q["end_ms"] = _zigzag_or_signed(v2)
            elif f2 == 3:  # LabelMatcher{type=1, name=2, value=3}
                mtype, mname, mval = 0, "", ""
                for f3, _wt3, v3 in _pb_fields(v2):
                    if f3 == 1:
                        mtype = v3
                    elif f3 == 2:
                        mname = v3.decode("utf-8")
                    elif f3 == 3:
                        mval = v3.decode("utf-8")
                op = _READ_MATCHER_OPS.get(mtype)
                if op is None:
                    raise InvalidArguments(
                        f"unknown matcher type {mtype}")
                q["matchers"].append((op, mname, mval))
        queries.append(q)
    return queries


from greptimedb_tpu.utils.proto import (  # the ONE wire encoder
    pb_len as _pb_len, pb_tag as _pb_tag, pb_varint as _pb_varint,
)


def encode_read_response(
    results: list[list[tuple[dict, list[tuple[float, int]]]]],
) -> bytes:
    """[(labels, [(value, ts_ms), ...]), ...] per query →
    prometheus.ReadResponse bytes (caller snappy-compresses)."""
    import struct

    out = bytearray()
    for series_list in results:
        qr = bytearray()
        for labels, samples in series_list:
            ts_msg = bytearray()
            for name in sorted(labels):
                lab = _pb_len(1, name.encode()) + _pb_len(
                    2, str(labels[name]).encode())
                ts_msg += _pb_len(1, lab)
            for value, ts in samples:
                smp = (_pb_tag(1, 1) + struct.pack("<d", float(value))
                       + _pb_tag(2, 0) + _pb_varint(int(ts) & ((1 << 64) - 1)))
                ts_msg += _pb_len(2, smp)
            qr += _pb_len(1, bytes(ts_msg))
        out += _pb_len(1, bytes(qr))
    return bytes(out)
