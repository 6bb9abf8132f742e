"""Round-13 vectorized wire→device ingest pipeline.

Covers the PR's acceptance surface:

- bit-exact vectorized-vs-legacy parse parity for all three metric wire
  formats (escapes, quoted strings, NaN/inf, unicode tags, out-of-order
  timestamps, ragged schemas — the shapes that route through the
  row-at-a-time fallback must produce the same columns the legacy path
  yields, and the clean shapes must pin the object-decode counter at 0)
- end-to-end table-content parity: the same wire body ingested through
  the vectorized decoders and the row-at-a-time ``*_legacy`` oracles
  produces identical SQL results
- WAL group commit: concurrent appenders share one fsync, acked records
  survive a kill (no close/flush) and replay losslessly, torn tails
  still repair
- hot-tail grid catch-up: freshly acked rows extend the resident grid
  in place (cache event ``hot_tail``) and are queryable before any flush
- per-tenant write budgets: over-quota ingest surfaces as 503/429, the
  same error surface queries get
"""

import math
import struct
import threading

import numpy as np
import pytest

from greptimedb_tpu.datatypes.schema import ColumnSchema, Schema
from greptimedb_tpu.datatypes.types import ConcreteDataType as T
from greptimedb_tpu.datatypes.types import SemanticType as S
from greptimedb_tpu.servers.protocols import (
    parse_line_protocol, parse_line_protocol_legacy, parse_remote_write,
    parse_remote_write_legacy,
)
from greptimedb_tpu.standalone import GreptimeDB
from greptimedb_tpu.utils.proto import pb_len as _pb_len
from greptimedb_tpu.utils.proto import pb_varint as _pb_varint
from greptimedb_tpu.utils.telemetry import REGISTRY


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _norm(tables):
    """Parser output → plain lists (container-agnostic comparison)."""
    out = {}
    for t, cols in tables.items():
        norm = {}
        for k, v in cols.items():
            if k in ("__tags__", "__fields__"):
                norm[k] = list(v)
            elif hasattr(v, "materialize"):
                norm[k] = list(v.materialize())
            else:
                norm[k] = list(v)
        out[t] = norm
    return out


def _assert_tables_equal(a, b):
    assert set(a) == set(b)
    for t in a:
        assert set(a[t]) == set(b[t]), f"column sets differ for {t}"
        for k in a[t]:
            va, vb = a[t][k], b[t][k]
            assert len(va) == len(vb), f"{t}.{k} length"
            for i, (x, y) in enumerate(zip(va, vb)):
                fx = isinstance(x, (float, np.floating))
                fy = isinstance(y, (float, np.floating))
                if fx and fy and math.isnan(x) and math.isnan(y):
                    continue
                assert x == y, f"{t}.{k}[{i}]: {x!r} != {y!r}"


def _parse_lp_both(body, precision="ns"):
    vec = _norm(parse_line_protocol(body, precision))
    txt = body.decode("utf-8") if isinstance(body, bytes) else body
    legacy = _norm(parse_line_protocol_legacy(txt, precision))
    return vec, legacy


def _write_request(series):
    """[(labels_dict, [(val, ts_ms), ...]), ...] → WriteRequest bytes."""
    body = b""
    for labels, samples in series:
        ts_msg = b""
        for name, value in labels.items():
            label = _pb_len(1, name.encode()) + _pb_len(2, value.encode())
            ts_msg += _pb_len(1, label)
        for val, ts in samples:
            sample = (
                _pb_varint((1 << 3) | 1) + struct.pack("<d", val)
                + _pb_varint(2 << 3) + _pb_varint(ts & ((1 << 64) - 1))
            )
            ts_msg += _pb_len(2, sample)
        body += _pb_len(1, ts_msg)
    return body


def _otlp_gauge_request(points):
    """[(metric, attrs_dict, ts_ns, val), ...] → OTLP metrics bytes."""
    def kv(key, sval):
        anyv = _pb_len(1, sval.encode())
        return _pb_len(1, key.encode()) + _pb_len(2, anyv)

    def fixed64(field, val_bytes):
        return _pb_varint((field << 3) | 1) + val_bytes

    per_metric = {}
    for metric, attrs, ts_ns, val in points:
        pt = b"".join(_pb_len(7, kv(k, v)) for k, v in attrs.items())
        pt += fixed64(3, struct.pack("<Q", ts_ns))
        pt += fixed64(4, struct.pack("<d", val))
        per_metric.setdefault(metric, b"")
        per_metric[metric] += _pb_len(1, pt)
    scope_metrics = b""
    for metric, pts in per_metric.items():
        scope_metrics += _pb_len(
            2, _pb_len(1, metric.encode()) + _pb_len(5, pts))
    rm = _pb_len(2, scope_metrics)
    return _pb_len(1, rm)


# ---------------------------------------------------------------------------
# line protocol: vectorized vs legacy parse parity
# ---------------------------------------------------------------------------

class TestLineProtocolParity:
    def test_clean_batch_and_object_decode_pin(self):
        body = (
            b"cpu,host=a,dc=east usage=1.5,load=0.25 1000000\n"
            b"cpu,host=b,dc=west usage=2.5,load=0.5 2000000\n"
            b"cpu,host=a,dc=east usage=3.5,load=0.75 3000000\n"
        )
        before = REGISTRY.value(
            "greptime_ingest_object_decode_rows_total", ("influxdb",))
        vec = parse_line_protocol(body, "ns")
        after = REGISTRY.value(
            "greptime_ingest_object_decode_rows_total", ("influxdb",))
        # the vectorized hot path materializes ZERO rows through the
        # object decoder
        assert after - before == 0
        # and the tag column really is dictionary-coded
        assert hasattr(vec["cpu"]["host"], "codes")
        assert list(vec["cpu"]["host"].values) in (
            ["a", "b"], ["b", "a"])
        legacy = parse_line_protocol_legacy(body.decode(), "ns")
        _assert_tables_equal(_norm(vec), _norm(legacy))

    def test_fallback_counts_object_rows(self):
        before = REGISTRY.value(
            "greptime_ingest_object_decode_rows_total", ("influxdb",))
        parse_line_protocol(b'cpu value="quoted string" 1000000\n', "ns")
        after = REGISTRY.value(
            "greptime_ingest_object_decode_rows_total", ("influxdb",))
        assert after - before == 1

    @pytest.mark.parametrize("body", [
        # escapes: comma/space/equals inside identifiers → legacy fallback
        b"cpu,host=a\\ b usage=1 1000000\ncpu,host=c\\,d usage=2 2000000\n",
        # quoted string fields
        b'logs,app=web msg="hello, world",n=1i 1000000\n',
        # ragged schemas (None-filled by the legacy union)
        b"cpu,host=a usage=1 1000000\ncpu usage=2,load=3 2000000\n",
        # comment + blank lines
        b"# a comment\n\ncpu,host=a usage=1 1000000\n",
    ])
    def test_fallback_shapes_parity(self, body):
        vec, legacy = _parse_lp_both(body)
        _assert_tables_equal(vec, legacy)

    @pytest.mark.parametrize("body", [
        # NaN / inf field values (legacy float() semantics)
        b"m,host=a v=nan 1000000\nm,host=b v=inf 2000000\n"
        b"m,host=c v=-inf 3000000\n",
        # unicode tag values and keys survive byte-level transforms
        "m,host=héllo™,zone=日本 v=1.5 1000000\n"
        "m,host=café,zone=日本 v=2.5 2000000\n".encode(),
        # out-of-order + duplicate timestamps
        b"m,host=a v=3 3000000\nm,host=a v=1 1000000\nm,host=a v=1 1000000\n",
        # integer (i-suffix), unsigned (u-suffix) and bool fields
        b"m,host=a n=42i,u=7u,ok=true,v=1.5 1000000\n"
        b"m,host=b n=-9i,u=0u,ok=f,v=2.5 2000000\n",
        # negative timestamps (pre-epoch) and multiple measurements
        b"m1,host=a v=1 -1000000\nm2,host=b v=2 1000000\n"
        b"m1,host=c v=3 2000000\n",
        # no-tag lines
        b"m v=1 1000000\nm v=2 2000000\n",
    ])
    def test_value_shapes_parity(self, body):
        vec, legacy = _parse_lp_both(body)
        _assert_tables_equal(vec, legacy)

    @pytest.mark.parametrize("precision", ["ns", "us", "ms", "s"])
    def test_precision_parity(self, precision):
        body = b"m,host=a v=1 1234567891\nm,host=b v=2 -987654321\n"
        vec, legacy = _parse_lp_both(body, precision)
        _assert_tables_equal(vec, legacy)

    def test_errors_match_legacy(self):
        from greptimedb_tpu.errors import InvalidArguments

        for bad in (b"cpu_no_fields 1000\n", b"cpu,tag v=1 1000\n"):
            with pytest.raises(InvalidArguments):
                parse_line_protocol(bad, "ns")


# ---------------------------------------------------------------------------
# remote write + OTLP: vectorized vs legacy parse parity
# ---------------------------------------------------------------------------

class TestRemoteWriteParity:
    def test_parity_with_ragged_labels(self):
        pb = _write_request([
            ({"__name__": "up", "job": "api", "pod": "pé1"},
             [(1.0, 1000), (0.0, 2000)]),
            ({"__name__": "up", "job": "web"}, [(float("nan"), 1500)]),
            ({"__name__": "lat", "job": "api"},
             [(0.25, 3000), (0.5, -500)]),
        ])
        vec = _norm(parse_remote_write(pb))
        legacy = _norm(parse_remote_write_legacy(pb))
        _assert_tables_equal(vec, legacy)
        # ragged label sets fill with "" on both paths
        assert vec["up"]["pod"] == ["pé1", "pé1", ""]

    def test_tag_columns_are_dictionary_coded(self):
        out = parse_remote_write(_write_request([
            ({"__name__": "up", "job": "api"}, [(1.0, i) for i in range(50)]),
            ({"__name__": "up", "job": "web"}, [(1.0, i) for i in range(50)]),
        ]))
        col = out["up"]["job"]
        assert hasattr(col, "codes") and len(col.values) == 2
        assert len(col) == 100


class TestOtlpParity:
    def test_parity(self):
        from greptimedb_tpu.servers.otlp import (
            _assemble_legacy, _walk_otlp_metrics, parse_otlp_metrics,
        )

        ts = 1700000000 * 10 ** 9
        pb = _otlp_gauge_request([
            ("cpu_usage", {"pod": "p1", "zone": "über"}, ts, 42.5),
            ("cpu_usage", {"pod": "p2", "zone": "über"}, ts + 10 ** 9,
             7.25),
            ("cpu_usage", {"pod": "p1", "zone": "über"}, ts - 10 ** 9,
             float("inf")),
            ("mem_usage", {"pod": "p1"}, ts, 1.5),
        ])
        vec = _norm(parse_otlp_metrics(pb))
        legacy = _norm(_assemble_legacy(_walk_otlp_metrics(pb)))
        _assert_tables_equal(vec, legacy)
        assert len(vec["cpu_usage"]["ts"]) == 3


# ---------------------------------------------------------------------------
# end-to-end: identical table contents through either path
# ---------------------------------------------------------------------------

class TestEndToEndParity:
    LP_BODY = (
        b"cpu,host=a,dc=east usage=1.5,n=42i,ok=true 1000000000\n"
        b"cpu,host=b,dc=west usage=2.5,n=-7i,ok=false 2000000000\n"
        b"cpu,host=c,dc=east usage=nan,n=0i,ok=t 3000000000\n"
        b"mem,host=a free=0.25 1000000000\n"
    )

    def _ingest_and_dump(self, legacy: bool):
        from greptimedb_tpu.servers.http import _ingest_columns

        db = GreptimeDB()
        try:
            tables = (parse_line_protocol_legacy(self.LP_BODY.decode(), "ns")
                      if legacy else parse_line_protocol(self.LP_BODY, "ns"))
            for table, cols in tables.items():
                _ingest_columns(db, table, cols)
            dump = {}
            for t in ("cpu", "mem"):
                res = db.sql(f"SELECT * FROM {t} ORDER BY ts")
                dump[t] = (res.column_names, res.rows)
            return dump
        finally:
            db.close()

    def test_sql_contents_identical(self):
        vec = self._ingest_and_dump(legacy=False)
        legacy = self._ingest_and_dump(legacy=True)
        assert set(vec) == set(legacy)
        for t in vec:
            assert vec[t][0] == legacy[t][0]
            assert len(vec[t][1]) == len(legacy[t][1])
            for ra, rb in zip(vec[t][1], legacy[t][1]):
                for x, y in zip(ra, rb):
                    if (isinstance(x, float) and isinstance(y, float)
                            and math.isnan(x) and math.isnan(y)):
                        continue
                    assert x == y


# ---------------------------------------------------------------------------
# WAL group commit
# ---------------------------------------------------------------------------

def _wal_records(wal, frm=0):
    return list(wal.replay(frm))


class TestGroupCommitWal:
    def test_batched_flush_single_fsync(self, tmp_path):
        from greptimedb_tpu.storage.wal import FileLogStore

        wal = FileLogStore(str(tmp_path / "wal"), sync=True,
                           group_commit=True)
        f0 = REGISTRY.value("greptime_ingest_wal_fsyncs_total")
        waits = [wal.append_async(i, b"p%d" % i) for i in range(1, 9)]
        for w in waits:
            w()
        # all 8 records enqueued before the first leader flushed →
        # they share ONE buffered write + fsync (maybe 2 if the first
        # leader raced in early), never one per record
        fsyncs = REGISTRY.value("greptime_ingest_wal_fsyncs_total") - f0
        assert 1 <= fsyncs <= 2
        assert [s for s, _ in _wal_records(wal)] == list(range(1, 9))

    def test_concurrent_appenders_acked_then_killed_lose_nothing(
            self, tmp_path):
        from greptimedb_tpu.storage.wal import FileLogStore

        wal = FileLogStore(str(tmp_path / "wal"), sync=True,
                           group_commit=True)
        acked: list[int] = []
        lock = threading.Lock()

        def writer(base):
            for i in range(25):
                seq = base + i
                wal.append(seq, b"payload-%d" % seq)
                with lock:
                    acked.append(seq)

        threads = [threading.Thread(target=writer, args=(w * 1000,))
                   for w in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(acked) == 150
        # kill: no close(), no flush call — a fresh store must replay
        # every acked record (group commit acks only after durability)
        wal2 = FileLogStore(str(tmp_path / "wal"))
        got = {s for s, _ in _wal_records(wal2)}
        assert got == set(acked)

    def test_torn_tail_still_repairs(self, tmp_path):
        from greptimedb_tpu.storage.wal import FileLogStore

        wal = FileLogStore(str(tmp_path / "wal"), sync=True,
                           group_commit=True)
        wal.append(1, b"alpha")
        wal.append(2, b"beta")
        seg = wal._seg_path(wal._current_id)
        with open(seg, "ab") as fh:
            fh.write(b"\x40\x00\x00\x00torn")  # truncated record
        wal2 = FileLogStore(str(tmp_path / "wal"))
        assert [s for s, _ in _wal_records(wal2)] == [1, 2]

    def test_group_commit_off_is_synchronous(self, tmp_path):
        from greptimedb_tpu.storage.wal import FileLogStore

        wal = FileLogStore(str(tmp_path / "wal"), group_commit=False)
        assert wal._gc is None
        wal.append(1, b"solo")
        w = wal.append_async(2, b"async-solo")
        w()
        assert [s for s, _ in _wal_records(wal)] == [1, 2]

    def test_region_kill_replay_under_concurrent_ingest(self, tmp_data_dir):
        from greptimedb_tpu.storage import RegionEngine

        schema = Schema((
            ColumnSchema("host", T.STRING, S.TAG),
            ColumnSchema("ts", T.TIMESTAMP_MILLISECOND, S.TIMESTAMP,
                         nullable=False),
            ColumnSchema("v", T.FLOAT64, S.FIELD),
        ))
        eng = RegionEngine(tmp_data_dir)
        r = eng.create_region(1, schema)

        def writer(w):
            for i in range(10):
                r.write({"host": [f"h{w}"] * 4,
                         "ts": [w * 10 ** 6 + i * 1000 + j for j in range(4)],
                         "v": [float(w * 100 + i)] * 4})

        threads = [threading.Thread(target=writer, args=(w,))
                   for w in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # crash without flush: reopen replays the group-committed WAL
        eng2 = RegionEngine(tmp_data_dir)
        r2 = eng2.open_region(1)
        host = r2.scan_host()
        assert len(host["ts"]) == 4 * 10 * 4
        eng2.close()
        eng.close()


# ---------------------------------------------------------------------------
# hot-tail grid catch-up
# ---------------------------------------------------------------------------

class TestHotTail:
    def test_acked_rows_extend_resident_grid_before_flush(self):
        from greptimedb_tpu.servers.http import _ingest_columns

        db = GreptimeDB()
        try:
            db.sql(
                "CREATE TABLE cpu (host STRING, ts TIMESTAMP(3) TIME INDEX,"
                " v DOUBLE, PRIMARY KEY (host))")
            base = 1451606400000
            rows = ", ".join(
                f"('h{h}', {base + i * 1000}, {h + i}.0)"
                for h in range(4) for i in range(64))
            db.sql("INSERT INTO cpu VALUES " + rows)
            region = db._regions_of("public.cpu")[0]
            grid = db.cache.get_grid(region)
            assert grid is not None, "grid not resident (test premise)"
            db.cache.min_extend_rows = 1  # don't let small deltas skip
            h0 = REGISTRY.value(
                "greptime_cache_events_total",
                ("region_device", "grid", "hot_tail"))
            flushes_before = len(region.sst_files)
            cols = parse_line_protocol(
                "".join(
                    f"cpu,host=h{h} v={h + 99}.5 "
                    f"{(base + 100_000 + h * 1000) * 1_000_000}\n"
                    for h in range(4)).encode(), "ns")["cpu"]
            _ingest_columns(db, "cpu", cols)
            h1 = REGISTRY.value(
                "greptime_cache_events_total",
                ("region_device", "grid", "hot_tail"))
            assert h1 - h0 == 1, "ingest did not hot-tail the resident grid"
            assert len(region.sst_files) == flushes_before  # no flush
            # the extended grid is CURRENT: a fresh get_grid is a pure hit
            hits0 = db.cache.hits
            assert db.cache.get_grid(region) is not None
            assert db.cache.hits == hits0 + 1
            # and SQL sees the freshly acked rows
            res = db.sql("SELECT count(*), max(v) FROM cpu")
            assert res.rows[0][0] == 4 * 64 + 4
            assert res.rows[0][1] == 102.5
        finally:
            db.close()

    def test_promql_sees_hot_rows(self):
        db = GreptimeDB()
        try:
            pb = _write_request([
                ({"__name__": "up", "job": "api"},
                 [(1.0, 1000 + i * 1000) for i in range(30)]),
            ])
            from greptimedb_tpu.servers.protocols import (
                parse_remote_write as prw,
            )

            for name, cols in prw(pb).items():
                db.metric_engine.write(name, cols)
            r1 = db.sql("TQL EVAL (30, 30, '10') up")
            n1 = len(r1.rows)
            # second batch lands purely in memtable/append-log (no flush)
            pb2 = _write_request([
                ({"__name__": "up", "job": "web"}, [(2.0, 30_000)]),
            ])
            for name, cols in prw(pb2).items():
                db.metric_engine.write(name, cols)
            r2 = db.sql("TQL EVAL (30, 30, '10') up")
            assert len(r2.rows) == n1 + 1
        finally:
            db.close()


# ---------------------------------------------------------------------------
# tenant write budgets
# ---------------------------------------------------------------------------

class TestTenantWriteBudget:
    def test_over_quota_ingest_rejected(self):
        import urllib.error
        import urllib.request

        from greptimedb_tpu.servers import HttpServer

        db = GreptimeDB()
        srv = HttpServer(db, port=0)
        try:
            srv.start()
            assert db.scheduler is not None
            adm = db.scheduler.admission
            adm.set_quota("smallwriter", mem_bytes=64)
            adm.set_quota("slowwriter", qps=0.001, burst=1)
            body = b"cpu,host=a v=1 1000000\n" * 64

            def post(tenant):
                req = urllib.request.Request(
                    f"http://127.0.0.1:{srv.port}/v1/influxdb/write",
                    data=body, method="POST",
                    headers={"x-greptime-tenant": tenant})
                try:
                    with urllib.request.urlopen(req, timeout=10) as resp:
                        return resp.status
                except urllib.error.HTTPError as e:
                    return e.code

            # memory budget: decoded-batch estimate >> 64 bytes → 503
            assert post("smallwriter") == 503
            # rate budget: first write spends the only token → 429 next
            assert post("slowwriter") == 204
            assert post("slowwriter") == 429
            # an unlimited tenant still ingests
            assert post("default") == 204
        finally:
            srv.stop()
            db.close()


# ---------------------------------------------------------------------------
# Arrow IPC bulk insert (the standalone surface of the Flight do_put plane)
# ---------------------------------------------------------------------------

def _ipc(cols: dict) -> bytes:
    import io

    import pyarrow as pa

    t = pa.table(cols)
    sink = io.BytesIO()
    with pa.ipc.new_stream(sink, t.schema) as w:
        w.write_table(t)
    return sink.getvalue()


class TestArrowBulkParity:
    def _mixed_body(self):
        import pyarrow as pa

        return _ipc({
            "hostname": pa.array(
                ["h1", "h2", "hé世"]).dictionary_encode(),
            "dc": ["east", "west", "ea,st \"q\""],
            "ts": np.array([3000, 1000, 2000], dtype=np.int64),  # unordered
            "usage": np.array([1.5, float("nan"), float("inf")]),
            "count": np.array([1, -7, 2**53], dtype=np.int64),
            "ok": np.array([True, False, True]),
        })

    @staticmethod
    def _object_columns(body):
        """The plain reference decode: every column a list of Python
        objects (None for null), ts as epoch ms, tags by arrow type."""
        import pyarrow as pa

        table = pa.ipc.open_stream(body).read_all()
        cols, tags, fields = {}, [], []
        for name in table.column_names:
            col = table.column(name)
            if name == "ts":
                if pa.types.is_timestamp(col.type):
                    col = col.cast(pa.timestamp("ms")).cast(pa.int64())
                cols[name] = col.to_pylist()
                continue
            stringish = (pa.types.is_dictionary(col.type)
                         or pa.types.is_string(col.type))
            (tags if stringish else fields).append(name)
            cols[name] = col.to_pylist()
        cols["__tags__"] = sorted(tags)
        cols["__fields__"] = sorted(fields)
        return cols

    def _dump(self, body, reference: bool, table="m"):
        from greptimedb_tpu.servers.http import _ingest_columns
        from greptimedb_tpu.servers.protocols import parse_arrow_bulk

        db = GreptimeDB()
        try:
            _ingest_columns(db, table, self._object_columns(body)
                            if reference else parse_arrow_bulk(body))
            res = db.sql(f"SELECT * FROM {table} ORDER BY ts")
            return res.column_names, res.rows
        finally:
            db.close()

    def _assert_rows_equal(self, vec, legacy):
        assert vec[0] == legacy[0]
        assert len(vec[1]) == len(legacy[1])
        for ra, rb in zip(vec[1], legacy[1]):
            for x, y in zip(ra, rb):
                if (isinstance(x, float) and isinstance(y, float)
                        and math.isnan(x) and math.isnan(y)):
                    continue
                assert x == y, (vec, legacy)

    def test_sql_contents_identical_and_decode_pin(self):
        from greptimedb_tpu.servers.protocols import parse_arrow_bulk

        body = self._mixed_body()
        d0 = REGISTRY.value("greptime_ingest_object_decode_rows_total",
                            ("arrow",))
        vec = self._dump(body, reference=False)
        # the null-free mixed-type body never touches the object path
        assert REGISTRY.value("greptime_ingest_object_decode_rows_total",
                              ("arrow",)) == d0
        legacy = self._dump(body, reference=True)
        self._assert_rows_equal(vec, legacy)
        # tags classified from arrow types
        cols = parse_arrow_bulk(body)
        assert cols["__tags__"] == ["dc", "hostname"]
        assert cols["__fields__"] == ["count", "ok", "usage"]

    def test_null_columns_take_object_path_with_parity(self):
        import pyarrow as pa

        body = _ipc({
            "host": pa.array(["a", None, "c"]),
            "ts": np.array([1, 2, 3], dtype=np.int64),
            "v": pa.array([1.0, None, 3.0]),
            "n": pa.array([None, 5, 6], type=pa.int64()),
        })
        d0 = REGISTRY.value("greptime_ingest_object_decode_rows_total",
                            ("arrow",))
        vec = self._dump(body, reference=False)
        assert REGISTRY.value("greptime_ingest_object_decode_rows_total",
                              ("arrow",)) == d0 + 3
        legacy = self._dump(body, reference=True)
        self._assert_rows_equal(vec, legacy)
        # None survived to NULL (floats NaN→NULL; null tags render '')
        names, rows = vec
        assert rows[1][names.index("v")] is None
        assert rows[1][names.index("host")] == ""

    def test_null_dictionary_vocab_entry(self):
        import pyarrow as pa

        dic = pa.DictionaryArray.from_arrays(
            pa.array([0, 1, 0], type=pa.int32()),
            pa.array(["x", None]))
        body = _ipc({"tag": dic, "ts": np.array([1, 2, 3], dtype=np.int64),
                     "v": np.array([1.0, 2.0, 3.0])})
        vec = self._dump(body, reference=False)
        legacy = self._dump(body, reference=True)
        self._assert_rows_equal(vec, legacy)
        # row 2's vocab entry is null → NULL tag renders '' on both paths
        assert vec[1][1][vec[0].index("tag")] == ""

    def test_timestamp_typed_ts(self):
        import pyarrow as pa

        body = _ipc({
            "host": ["a", "b"],
            "ts": pa.array([1_000_000, 2_000_000], type=pa.timestamp("us")),
            "v": np.array([1.0, 2.0]),
        })
        vec = self._dump(body, reference=False)
        legacy = self._dump(body, reference=True)
        self._assert_rows_equal(vec, legacy)
        assert [r[1] for r in vec[1]] == [1000, 2000]  # us → ms

    def test_bad_bodies_rejected(self):
        from greptimedb_tpu.errors import InvalidArguments
        from greptimedb_tpu.servers.protocols import parse_arrow_bulk

        with pytest.raises(InvalidArguments, match="arrow ipc"):
            parse_arrow_bulk(b"not an ipc stream")
        with pytest.raises(InvalidArguments, match="'ts'"):
            parse_arrow_bulk(_ipc({"v": np.array([1.0])}))
        with pytest.raises(InvalidArguments, match="ts"):
            parse_arrow_bulk(_ipc({"ts": ["not-a-time"],
                                   "v": np.array([1.0])}))


# ---------------------------------------------------------------------------
# slim WAL payload format (no __tsid__/__seq__/__op__ columns)
# ---------------------------------------------------------------------------

class TestSlimWalFormat:
    def _schema(self):
        return Schema((
            ColumnSchema("host", T.STRING, S.TAG),
            ColumnSchema("ts", T.TIMESTAMP_MILLISECOND, S.TIMESTAMP,
                         nullable=False),
            ColumnSchema("v", T.FLOAT64, S.FIELD),
        ))

    def test_payload_carries_only_schema_columns(self, tmp_data_dir):
        from greptimedb_tpu.storage import RegionEngine
        from greptimedb_tpu.storage.wal import decode_write_full

        eng = RegionEngine(tmp_data_dir)
        region = eng.create_region(1, self._schema())
        region.write({"host": ["a"], "ts": [1], "v": [1.0]})
        recs = list(region.wal.replay(0))
        assert len(recs) == 1
        cols, op = decode_write_full(recs[0][1])
        assert sorted(cols) == ["host", "ts", "v"]
        assert op == 0

    def test_delete_op_rides_metadata_through_replay(self, tmp_data_dir):
        from greptimedb_tpu.storage import RegionEngine
        from greptimedb_tpu.storage.memtable import OP_DELETE

        eng = RegionEngine(tmp_data_dir)
        region = eng.create_region(1, self._schema())
        region.write({"host": ["a", "b"], "ts": [1, 1], "v": [1.0, 2.0]})
        region.write({"host": ["a"], "ts": [1], "v": [0.0]}, op=OP_DELETE)
        # kill (no flush) → reopen replays both batches; the tombstone
        # must still shadow host=a
        eng2 = RegionEngine(tmp_data_dir)
        r2 = eng2.open_region(1, self._schema())
        got = r2.memtable.freeze()
        live = [(h, int(o)) for h, o in zip(got["host"], got["__op__"])]
        assert ("a", OP_DELETE) in live and ("b", 0) in live
        srows = r2.scan_host()
        assert list(srows["host"]) == ["b"]


class TestWirePassthroughWal:
    """Arrow-bulk wire bytes logged verbatim as the WAL payload.

    A structurally-clean bulk body (int64 ms ts, no nulls, every schema
    column present) IS a valid slim payload — the region must log the
    wire stream byte-for-byte (no re-serialization) and replay it to the
    same table contents; any mismatch with the schema must fall back to
    the encoded slim payload."""

    def _schema(self):
        return Schema((
            ColumnSchema("host", T.STRING, S.TAG),
            ColumnSchema("ts", T.TIMESTAMP_MILLISECOND, S.TIMESTAMP,
                         nullable=False),
            ColumnSchema("v", T.FLOAT64, S.FIELD),
        ))

    def _body(self):
        import pyarrow as pa

        return _ipc({
            "host": pa.array(["a", "b", "a"]).dictionary_encode(),
            "ts": np.array([1000, 1000, 2000], dtype=np.int64),
            "v": np.array([1.5, 2.5, 3.5]),
        })

    def _write_parsed(self, region, body):
        from greptimedb_tpu.servers.protocols import parse_arrow_bulk

        cols = parse_arrow_bulk(body)
        cols.pop("__tags__"), cols.pop("__fields__")
        wire = cols.pop("__wire_ipc__", None)
        region.write(cols, wire_payload=wire)
        return wire

    def test_wire_bytes_logged_verbatim_and_replayed(self, tmp_data_dir):
        from greptimedb_tpu.storage import RegionEngine

        body = self._body()
        eng = RegionEngine(tmp_data_dir)
        region = eng.create_region(1, self._schema())
        wire = self._write_parsed(region, body)
        assert wire is not None  # parser offered the passthrough
        recs = list(region.wal.replay(0))
        assert len(recs) == 1 and recs[0][1] == body  # logged verbatim
        # kill (no flush/close) → replay re-derives codes/tsids from the
        # raw wire stream; contents must match what was acked
        eng2 = RegionEngine(tmp_data_dir)
        r2 = eng2.open_region(1, self._schema())
        got = r2.scan_host()
        rows = sorted(zip(got["host"], got["ts"], got["v"]))
        assert rows == [("a", 1000, 1.5), ("a", 2000, 3.5),
                        ("b", 1000, 2.5)]

    def test_schema_wider_than_wire_falls_back(self, tmp_data_dir):
        from greptimedb_tpu.storage import RegionEngine

        schema = Schema(self._schema().columns + (
            ColumnSchema("w", T.FLOAT64, S.FIELD),))
        body = self._body()
        eng = RegionEngine(tmp_data_dir)
        region = eng.create_region(1, schema)
        self._write_parsed(region, body)
        recs = list(region.wal.replay(0))
        # default-filled column w is NOT in the wire bytes: the region
        # must have logged the encoded slim payload instead
        assert recs[0][1] != body
        eng2 = RegionEngine(tmp_data_dir)
        r2 = eng2.open_region(1, schema)
        assert len(r2.scan_host()["ts"]) == 3

    def test_end_to_end_kill_replay_through_http_surface(self, tmp_data_dir):
        from greptimedb_tpu.servers.http import _ingest_columns
        from greptimedb_tpu.servers.protocols import parse_arrow_bulk

        db = GreptimeDB(data_home=tmp_data_dir)
        _ingest_columns(db, "pt", parse_arrow_bulk(self._body()))
        rows = db.sql("SELECT host, ts, v FROM pt ORDER BY ts, host").rows
        # kill: no close/flush — a second instance replays the WAL
        db2 = GreptimeDB(data_home=tmp_data_dir)
        try:
            assert db2.sql(
                "SELECT host, ts, v FROM pt ORDER BY ts, host").rows == rows
        finally:
            db2.close()
