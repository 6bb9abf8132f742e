"""OTLP/HTTP metrics ingest: hand-rolled protobuf wire parsing.

Reference: src/servers/src/otlp/metrics.rs — OTel metrics map to tables:
gauge/sum data points land in a table named after the metric (attributes →
tags, value → ``val``); histograms explode prometheus-style into
``<name>_bucket`` (cumulative counts with an ``le`` tag), ``<name>_sum`` and
``<name>_count`` tables, which makes ``histogram_quantile`` work unchanged.

Wire schema walked here (opentelemetry-proto, metrics/v1):
ExportMetricsServiceRequest.resource_metrics[1] → ResourceMetrics{
resource[1]{attributes[1]}, scope_metrics[2]{metrics[2]}} → Metric{name[1],
gauge[5]/sum[7]/histogram[9]} → NumberDataPoint{attributes[7],
time_unix_nano[3], as_double[4], as_int[6]} / HistogramDataPoint{
attributes[9], time_unix_nano[3], count[4], sum[5], bucket_counts[6],
explicit_bounds[7]}.
"""

from __future__ import annotations

import struct
from collections import defaultdict

from greptimedb_tpu.servers.protocols import _pb_fields


def parse_any_value(data: bytes):
    """opentelemetry.proto.common.v1.AnyValue → typed python value,
    including composites (array[5], kvlist[6], bytes[7]) — log/span
    attributes carry them and logs.rs preserves them."""
    for f, _wt, v in _pb_fields(data):
        if f == 1:
            return v.decode("utf-8", "replace")
        if f == 2:
            return bool(v)
        if f == 3:
            return _signed(v)
        if f == 4:
            return struct.unpack("<d", v)[0]
        if f == 5:  # ArrayValue{values=1}
            return [parse_any_value(x) for ff, _w, x in _pb_fields(v)
                    if ff == 1]
        if f == 6:  # KeyValueList{values=1}
            out = {}
            for ff, _w, x in _pb_fields(v):
                if ff == 1:
                    k, val = parse_key_value(x)
                    out[k] = val
            return out
        if f == 7:  # bytes
            return v.hex()
    return None


def parse_key_value(data: bytes) -> tuple[str, object]:
    """opentelemetry.proto.common.v1.KeyValue → (key, typed value)."""
    key = ""
    value = None
    for f, _wt, v in _pb_fields(data):
        if f == 1:
            key = v.decode("utf-8", "replace")
        elif f == 2:
            value = parse_any_value(v)
    return key, value


def _kv_attr(data: bytes) -> tuple[str, str]:
    key, value = parse_key_value(data)
    if isinstance(value, bool):
        return key, "true" if value else "false"
    if isinstance(value, float):
        return key, repr(value)
    return key, "" if value is None else str(value)


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= (1 << 63) else v


def _fixed64_f(v: bytes) -> float:
    return struct.unpack("<d", v)[0]


def _fixed64_u(v: bytes) -> int:
    return struct.unpack("<Q", v)[0]


def _packed_doubles(v: bytes) -> list[float]:
    return [struct.unpack("<d", v[i:i + 8])[0] for i in range(0, len(v), 8)]


def _packed_fixed64(v: bytes) -> list[int]:
    return [struct.unpack("<Q", v[i:i + 8])[0] for i in range(0, len(v), 8)]


def _number_point(data: bytes) -> tuple[dict, float, int]:
    attrs: dict[str, str] = {}
    val = float("nan")
    ts_ms = 0
    for f, wt, v in _pb_fields(data):
        if f == 7:
            k, a = _kv_attr(v)
            attrs[k] = a
        elif f == 3:
            ts_ms = _fixed64_u(v) // 1_000_000
        elif f == 4:
            val = _fixed64_f(v)
        elif f == 6:
            # as_int: sfixed64
            val = float(struct.unpack("<q", v)[0])
    return attrs, val, ts_ms


def _histogram_point(data: bytes):
    attrs: dict[str, str] = {}
    ts_ms = 0
    count = 0
    total = float("nan")
    bucket_counts: list[int] = []
    bounds: list[float] = []
    for f, wt, v in _pb_fields(data):
        if f == 9:
            k, a = _kv_attr(v)
            attrs[k] = a
        elif f == 3:
            ts_ms = _fixed64_u(v) // 1_000_000
        elif f == 4:
            count = _fixed64_u(v)
        elif f == 5:
            total = _fixed64_f(v)
        elif f == 6:
            if wt == 2:
                bucket_counts = _packed_fixed64(v)
            else:  # legal unpacked repeated fixed64
                bucket_counts.append(_fixed64_u(v))
        elif f == 7:
            if wt == 2:
                bounds = _packed_doubles(v)
            else:
                bounds.append(_fixed64_f(v))
    return attrs, ts_ms, count, total, bucket_counts, bounds


def _norm(name: str) -> str:
    out = []
    for ch in name:
        out.append(ch if (ch.isalnum() or ch == "_") else "_")
    return "".join(out)


def parse_otlp_metrics(body: bytes) -> dict[str, dict[str, list]]:
    """ExportMetricsServiceRequest → per-table columnar dicts (same shape
    the line-protocol/remote-write parsers emit).

    The assembly is vectorized (``_assemble_vec``): data
    points carry self-describing attribute sets (the protobuf forces a
    per-POINT decode), but attribute sets repeat heavily across points,
    so they memoize into a per-table vocabulary and the per-row output is
    int32 indexes — tag columns come out as ``DictColumn`` with one
    ``np.take`` per tag instead of a per-row × per-tag Python loop
    (``_assemble_legacy``, the parity oracle of
    tests/test_ingest_pipeline.py)."""
    from greptimedb_tpu.servers.protocols import (
        M_INGEST_BATCHES, M_PARSE_SECONDS, TRACER,
    )

    with M_PARSE_SECONDS.labels("otlp_metrics").time(), \
            TRACER.stage("ingest_parse", protocol="otlp_metrics"):
        out = _assemble_vec(_walk_otlp_metrics(body))
        M_INGEST_BATCHES.labels("otlp_metrics", "vectorized").inc()
        return out


def _walk_otlp_metrics(body: bytes) -> dict[str, list]:
    """Protobuf walk → per-table point rows (shared by both assemblies)."""
    rows: dict[str, list[tuple[dict, float, int]]] = defaultdict(list)
    for f, _wt, rm in _pb_fields(body):
        if f != 1:
            continue
        resource_attrs: dict[str, str] = {}
        scope_metrics = []
        for f2, _wt2, v2 in _pb_fields(rm):
            if f2 == 1:  # Resource
                for f3, _wt3, v3 in _pb_fields(v2):
                    if f3 == 1:
                        k, a = _kv_attr(v3)
                        resource_attrs[k] = a
            elif f2 == 2:
                scope_metrics.append(v2)
        for sm in scope_metrics:
            for f3, _wt3, metric in _pb_fields(sm):
                if f3 != 2:
                    continue
                name = ""
                gauges = []
                hists = []
                for f4, _wt4, v4 in _pb_fields(metric):
                    if f4 == 1:
                        name = v4.decode("utf-8")
                    elif f4 in (5, 7):  # gauge / sum: points in field 1
                        for f5, _wt5, p in _pb_fields(v4):
                            if f5 == 1:
                                gauges.append(p)
                    elif f4 == 9:  # histogram
                        for f5, _wt5, p in _pb_fields(v4):
                            if f5 == 1:
                                hists.append(p)
                if not name:
                    continue
                table = _norm(name)
                for p in gauges:
                    attrs, val, ts_ms = _number_point(p)
                    merged = {**resource_attrs, **attrs}
                    rows[table].append((merged, val, ts_ms))
                for p in hists:
                    attrs, ts_ms, count, total, bcounts, bounds = (
                        _histogram_point(p)
                    )
                    merged = {**resource_attrs, **attrs}
                    cum = 0
                    for i, c in enumerate(bcounts):
                        cum += c
                        le = (
                            repr(bounds[i]) if i < len(bounds) else "+Inf"
                        )
                        rows[f"{table}_bucket"].append(
                            ({**merged, "le": le}, float(cum), ts_ms)
                        )
                    rows[f"{table}_sum"].append((merged, total, ts_ms))
                    rows[f"{table}_count"].append((merged, float(count), ts_ms))
    return rows


def _assemble_legacy(rows: dict[str, list]) -> dict[str, dict[str, list]]:
    """Row-at-a-time column assembly (the seed path, A/B + parity)."""
    out: dict[str, dict[str, list]] = {}
    for table, data in rows.items():
        tag_names = sorted(
            {_safe_tag(k) for tags, _v, _t in data for k in tags}
        )
        cols: dict[str, list] = {k: [] for k in tag_names}
        cols["ts"] = []
        cols["val"] = []
        for tags, val, ts in data:
            renamed = {_safe_tag(k): v for k, v in tags.items()}
            for k in tag_names:
                cols[k].append(renamed.get(k, ""))
            cols["ts"].append(ts)
            cols["val"].append(val)
        out[table] = {"__tags__": tag_names, "__fields__": ["val"], **cols}
    return out


def _assemble_vec(rows: dict[str, list]) -> dict[str, dict]:
    """Columnar assembly: attribute sets memoize into a per-table
    vocabulary (points of the same series share one entry), tag columns
    become ``DictColumn`` via one factorize + take per tag, values and
    timestamps convert in one C pass each — no per-row × per-tag Python
    loop."""
    import numpy as np
    import pandas as pd

    from greptimedb_tpu.datatypes.batch import DictColumn

    out: dict[str, dict] = {}
    for table, data in rows.items():
        memo: dict[tuple, int] = {}
        uniq: list[dict] = []
        uidx: list[int] = []
        vals: list[float] = []
        tss: list[int] = []
        for tags, val, ts in data:
            key = tuple(sorted(tags.items()))
            i = memo.get(key)
            if i is None:
                i = memo[key] = len(uniq)
                uniq.append({_safe_tag(k): v for k, v in tags.items()})
            uidx.append(i)
            vals.append(val)
            tss.append(ts)
        tag_names = sorted({k for d in uniq for k in d})
        uidx_np = np.asarray(uidx, dtype=np.int64)
        cols: dict[str, object] = {}
        for k in tag_names:
            per_u = np.asarray([d.get(k, "") for d in uniq], dtype=object)
            codes, uvals = pd.factorize(per_u)
            cols[k] = DictColumn(
                np.asarray(uvals, dtype=object),
                codes.astype(np.int32)[uidx_np],
            )
        cols["ts"] = np.asarray(tss, dtype=np.int64)
        cols["val"] = np.asarray(vals, dtype=np.float64)
        out[table] = {"__tags__": tag_names, "__fields__": ["val"], **cols}
    return out


def _safe_tag(k: str) -> str:
    """Attribute keys colliding with reserved output columns are renamed
    (an attribute literally named 'ts' or 'val' would corrupt the batch)."""
    return k + "_attr" if k in ("ts", "val") else k


# ---------------------------------------------------------------------------
# OTLP logs (reference src/servers/src/otlp/logs.rs)
# ---------------------------------------------------------------------------

def parse_otlp_logs(body: bytes) -> list[dict]:
    """ExportLogsServiceRequest → flat rows (reference logs.rs column
    model: timestamp, trace/span ids, severity, body, and the three
    attribute scopes as JSON strings).

    Wire: ExportLogsServiceRequest.resource_logs[1] → ResourceLogs{
    resource[1]{attributes[1]}, scope_logs[2]: ScopeLogs{scope[1]{name[1],
    version[2]}, log_records[2]: LogRecord{time_unix_nano[1] fixed64,
    severity_number[2], severity_text[3], body[5], attributes[6],
    flags[8] fixed32, trace_id[9], span_id[10],
    observed_time_unix_nano[11] fixed64}}}."""
    import json as _json

    rows: list[dict] = []
    for f, _wt, rl in _pb_fields(body):
        if f != 1:
            continue
        resource_attrs: dict = {}
        scope_logs = []
        for f2, _wt2, v2 in _pb_fields(rl):
            if f2 == 1:  # Resource
                for f3, _wt3, v3 in _pb_fields(v2):
                    if f3 == 1:
                        k, val = parse_key_value(v3)
                        resource_attrs[k] = val
            elif f2 == 2:
                scope_logs.append(v2)
        for sl in scope_logs:
            scope_name = scope_version = ""
            scope_attrs: dict = {}
            records = []
            for f2, _wt2, v2 in _pb_fields(sl):
                if f2 == 1:  # InstrumentationScope
                    for f3, _wt3, v3 in _pb_fields(v2):
                        if f3 == 1:
                            scope_name = v3.decode("utf-8", "replace")
                        elif f3 == 2:
                            scope_version = v3.decode("utf-8", "replace")
                        elif f3 == 3:
                            k, val = parse_key_value(v3)
                            scope_attrs[k] = val
                elif f2 == 2:
                    records.append(v2)
            for rec in records:
                ts_ns = obs_ns = 0
                sev_num = 0
                sev_text = ""
                body_val = None
                attrs: dict = {}
                flags = 0
                trace_id = span_id = ""
                for f3, wt3, v3 in _pb_fields(rec):
                    if f3 == 1:
                        ts_ns = _fixed64_u(v3)
                    elif f3 == 2:
                        sev_num = v3
                    elif f3 == 3:
                        sev_text = v3.decode("utf-8", "replace")
                    elif f3 == 5:
                        body_val = parse_any_value(v3)
                    elif f3 == 6:
                        k, val = parse_key_value(v3)
                        attrs[k] = val
                    elif f3 == 8:
                        flags = int.from_bytes(v3, "little") if (
                            isinstance(v3, bytes)) else int(v3)
                    elif f3 == 9:
                        trace_id = v3.hex()
                    elif f3 == 10:
                        span_id = v3.hex()
                    elif f3 == 11:
                        obs_ns = _fixed64_u(v3)
                ns = ts_ns or obs_ns
                rows.append({
                    "ts": ns // 1_000_000,
                    "trace_id": trace_id,
                    "span_id": span_id,
                    "trace_flags": int(flags),
                    "scope_name": scope_name,
                    "scope_version": scope_version,
                    "severity_number": int(sev_num),
                    "severity_text": sev_text,
                    "body": (body_val if isinstance(body_val, str)
                             else _json.dumps(body_val, ensure_ascii=False)),
                    "log_attributes": _json.dumps(attrs, ensure_ascii=False),
                    "scope_attributes": _json.dumps(scope_attrs,
                                                    ensure_ascii=False),
                    "resource_attributes": _json.dumps(resource_attrs,
                                                       ensure_ascii=False),
                })
    return rows
