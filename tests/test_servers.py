"""HTTP protocol server tests: real sockets, real wire formats.

Mirrors the reference's protocol integration tests
(tests-integration/tests/http.rs): SQL envelope, Prometheus API formats,
line protocol and remote write bodies.
"""

import json
import struct
import urllib.parse
import urllib.request

import numpy as np
import pytest

from greptimedb_tpu.servers import HttpServer
from greptimedb_tpu.servers.protocols import parse_line_protocol, parse_remote_write
from greptimedb_tpu.standalone import GreptimeDB
from greptimedb_tpu.utils import snappy


@pytest.fixture(scope="module")
def server():
    db = GreptimeDB()
    srv = HttpServer(db, port=0)
    srv.start()
    yield srv
    srv.stop()
    db.close()


def http(server, path, method="GET", body=None, headers=None, form=None):
    url = f"http://127.0.0.1:{server.port}{path}"
    if form is not None:
        body = urllib.parse.urlencode(form).encode()
        headers = dict(headers or {})
        headers["Content-Type"] = "application/x-www-form-urlencoded"
        method = "POST"
    req = urllib.request.Request(url, data=body, method=method,
                                 headers=headers or {})
    try:
        with urllib.request.urlopen(req) as resp:
            data = resp.read()
            return resp.status, data
    except urllib.error.HTTPError as e:
        return e.code, e.read()


class TestSnappy:
    def test_roundtrip(self):
        for payload in [b"", b"x", b"hello world" * 100, bytes(range(256)) * 50]:
            assert snappy.decompress(snappy.compress(payload)) == payload

    def test_copy_elements(self):
        # hand-built: literal "abcd" + 1-byte-offset copy of 4 from offset 4
        body = bytes([8]) + bytes([(4 - 1) << 2]) + b"abcd" + bytes(
            [0b001 | ((4 - 4) << 2)][0:1]
        )
        # tag: type=1, len=4 -> ((4-4)<<2)|1 = 1; offset byte = 4
        body = bytes([8, (4 - 1) << 2]) + b"abcd" + bytes([1, 4])
        assert snappy.decompress(body) == b"abcdabcd"

    def test_corrupt(self):
        with pytest.raises(ValueError):
            snappy.decompress(b"\x10\xff\xff")


class TestLineProtocol:
    def test_parse(self):
        out = parse_line_protocol(
            'cpu,host=h1,region=us value=0.5,count=3i 1700000000000000000\n'
            'cpu,host=h2 value=1.5 1700000001000000000\n'
            'mem,host=h1 used=12.5 1700000000000000000\n'
        )
        assert set(out) == {"cpu", "mem"}
        cpu = out["cpu"]
        assert cpu["__tags__"] == ["host", "region"]
        assert cpu["host"] == ["h1", "h2"]
        assert cpu["region"] == ["us", None]
        assert cpu["value"] == [0.5, 1.5]
        assert cpu["count"] == [3, None]
        assert cpu["ts"] == [1700000000000, 1700000001000]

    def test_escapes_and_types(self):
        out = parse_line_protocol(
            'my\\ table,tag=va\\,lue str="quoted \\"x\\"",b=t 1000',
            precision="ms",
        )
        t = out["my table"]
        assert t["tag"] == ["va,lue"]
        assert t["str"] == ['quoted "x"']
        assert t["b"] == [True]
        assert t["ts"] == [1000]

    def test_bad_lines(self):
        from greptimedb_tpu.errors import InvalidArguments

        for bad in ["cpu", "cpu,host=h1", "cpu value=", ",host=x value=1"]:
            with pytest.raises(InvalidArguments):
                parse_line_protocol(bad)


from greptimedb_tpu.utils.proto import (
    pb_len as _pb_len, pb_varint as _pb_varint,
)


def make_write_request(series: list[tuple[dict, list[tuple[float, int]]]]) -> bytes:
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


class TestRemoteWriteCodec:
    def test_parse(self):
        pb = make_write_request([
            ({"__name__": "up", "job": "api"}, [(1.0, 1000), (0.0, 2000)]),
            ({"__name__": "up", "job": "web"}, [(1.0, 1000)]),
        ])
        out = parse_remote_write(pb)
        assert set(out) == {"up"}
        up = out["up"]
        # container-agnostic: the vectorized parser returns np arrays /
        # DictColumn, the legacy (=off) parser plain lists — same VALUES
        assert list(up["job"]) == ["api", "api", "web"]
        assert list(up["val"]) == [1.0, 0.0, 1.0]
        assert list(up["ts"]) == [1000, 2000, 1000]


class TestHttpApi:
    def test_sql_roundtrip(self, server):
        code, _ = http(server, "/v1/sql", form={
            "sql": "CREATE TABLE web (host STRING, ts TIMESTAMP(3) TIME INDEX,"
                   " hits DOUBLE, PRIMARY KEY (host))"})
        assert code == 200
        code, _ = http(server, "/v1/sql", form={
            "sql": "INSERT INTO web VALUES ('a', 1000, 5.0), ('b', 2000, 7.0)"})
        assert code == 200
        code, raw = http(
            server,
            "/v1/sql?" + urllib.parse.urlencode(
                {"sql": "SELECT host, hits FROM web ORDER BY host"}),
        )
        assert code == 200
        body = json.loads(raw)
        assert body["code"] == 0
        rec = body["output"][0]["records"]
        assert [c["name"] for c in rec["schema"]["column_schemas"]] == ["host", "hits"]
        assert rec["rows"] == [["a", 5.0], ["b", 7.0]]

    def test_sql_errors(self, server):
        code, raw = http(server, "/v1/sql", form={"sql": "SELEC 1"})
        assert code == 400
        assert json.loads(raw)["code"] != 0
        code, raw = http(server, "/v1/sql", form={"sql": "SELECT * FROM nope"})
        assert code == 404
        code, raw = http(server, "/v1/sql")
        assert code == 400

    # Tests of this class read what an earlier one wrote to the module's
    # server; under xdist the earlier one may have run on another worker,
    # so each reader writes its rows itself (the same rows again change
    # nothing).
    @staticmethod
    def _write_weather(server):
        lp = (
            "weather,city=sf temp=13.5 1700000000000\n"
            "weather,city=nyc temp=2.0 1700000000000\n"
        )
        code, _ = http(server, "/v1/influxdb/api/v2/write?precision=ms",
                       method="POST", body=lp.encode())
        assert code == 204

    @staticmethod
    def _write_http_total(server):
        ts0 = 1700000000000
        pb = make_write_request([
            ({"__name__": "http_total", "job": "api"},
             [(float(5 * i), ts0 + i * 10_000) for i in range(60)]),
        ])
        code, _ = http(server, "/v1/prometheus/write", method="POST",
                       body=snappy.compress(pb),
                       headers={"Content-Encoding": "snappy"})
        assert code == 204
        return ts0

    def test_influx_write_and_query(self, server):
        self._write_weather(server)
        code, raw = http(server, "/v1/sql?" + urllib.parse.urlencode(
            {"sql": "SELECT city, temp FROM weather ORDER BY city"}))
        rows = json.loads(raw)["output"][0]["records"]["rows"]
        assert rows == [["nyc", 2.0], ["sf", 13.5]]

    def test_arrow_bulk_write_and_query(self, server):
        import io

        import pyarrow as pa

        t = pa.table({
            "city": pa.array(["sf", "nyc"]).dictionary_encode(),
            "ts": np.array([1700000000000, 1700000000000], dtype=np.int64),
            "temp": np.array([13.5, 2.0]),
        })
        sink = io.BytesIO()
        with pa.ipc.new_stream(sink, t.schema) as w:
            w.write_table(t)
        code, raw = http(server, "/v1/arrow/write?table=weather_bulk",
                         method="POST", body=sink.getvalue())
        assert code == 200
        assert json.loads(raw)["rows"] == 2
        code, raw = http(server, "/v1/sql?" + urllib.parse.urlencode(
            {"sql": "SELECT city, temp FROM weather_bulk ORDER BY city"}))
        rows = json.loads(raw)["output"][0]["records"]["rows"]
        assert rows == [["nyc", 2.0], ["sf", 13.5]]
        # missing ?table= and junk bodies surface as 400, not 500
        code, _ = http(server, "/v1/arrow/write", method="POST",
                       body=sink.getvalue())
        assert code == 400
        code, _ = http(server, "/v1/arrow/write?table=x", method="POST",
                       body=b"junk")
        assert code == 400

    def test_influx_schema_extension(self, server):
        self._write_weather(server)
        http(server, "/v1/influxdb/api/v2/write?precision=ms",
             method="POST", body=b"weather,city=sf humidity=80.0 1700000001000")
        code, raw = http(server, "/v1/sql?" + urllib.parse.urlencode(
            {"sql": "SELECT humidity FROM weather WHERE city = 'sf' ORDER BY ts"}))
        rows = json.loads(raw)["output"][0]["records"]["rows"]
        assert rows == [[None], [80.0]]

    def test_remote_write_and_prom_query(self, server):
        ts0 = self._write_http_total(server)
        q = urllib.parse.urlencode({
            "query": "rate(http_total[5m])",
            "start": str(ts0 / 1000 + 300), "end": str(ts0 / 1000 + 500),
            "step": "100",
        })
        code, raw = http(server, f"/v1/prometheus/api/v1/query_range?{q}")
        assert code == 200
        body = json.loads(raw)
        assert body["status"] == "success"
        series = body["data"]["result"]
        assert len(series) == 1
        assert series[0]["metric"] == {"job": "api"}
        for _t, v in series[0]["values"]:
            assert float(v) == pytest.approx(0.5, rel=1e-5)

    def test_prom_instant_query(self, server):
        self._write_http_total(server)
        q = urllib.parse.urlencode({
            "query": "http_total", "time": str(1700000000000 / 1000 + 590),
        })
        code, raw = http(server, f"/v1/prometheus/api/v1/query?{q}")
        body = json.loads(raw)
        assert body["data"]["resultType"] == "vector"
        assert len(body["data"]["result"]) == 1

    def test_prom_metadata(self, server):
        self._write_http_total(server)
        code, raw = http(server, "/v1/prometheus/api/v1/labels")
        data = json.loads(raw)["data"]
        assert "__name__" in data and "job" in data
        code, raw = http(server, "/v1/prometheus/api/v1/label/__name__/values")
        assert "http_total" in json.loads(raw)["data"]
        code, raw = http(server, "/v1/prometheus/api/v1/label/job/values")
        assert "api" in json.loads(raw)["data"]
        q = urllib.parse.urlencode({"match[]": "http_total"})
        code, raw = http(server, f"/v1/prometheus/api/v1/series?{q}")
        data = json.loads(raw)["data"]
        assert {"__name__": "http_total", "job": "api"} in data

    def test_promql_native_endpoint(self, server):
        self._write_http_total(server)
        q = urllib.parse.urlencode({
            "query": "http_total", "start": str(1700000000000 / 1000 + 100),
            "end": str(1700000000000 / 1000 + 100), "step": "60",
        })
        code, raw = http(server, f"/v1/promql?{q}")
        assert code == 200
        body = json.loads(raw)
        rec = body["output"][0]["records"]
        assert rec["schema"]["column_schemas"][0]["name"] == "job"

    def test_admin_endpoints(self, server):
        code, _ = http(server, "/health")
        assert code == 200
        code, raw = http(server, "/metrics")
        assert code == 200
        assert b"greptime_http_requests_total" in raw
        code, raw = http(server, "/config")
        assert code == 200 and b"data_home" in raw
        code, raw = http(server, "/status")
        assert code == 200 and b"devices" in raw and b"memory" in raw

    def test_dashboard_served(self, server):
        code, raw = http(server, "/dashboard")
        assert code == 200
        # self-contained page wired to the real endpoints
        assert b"<!doctype html>" in raw and b"greptimedb-tpu" in raw
        for endpoint in (b"/v1/sql", b"/v1/prometheus/api/v1/query_range",
                         b"/status"):
            assert endpoint in raw
        assert b'src="http' not in raw  # no external assets

    def test_bad_remote_write_body(self, server):
        code, _ = http(server, "/v1/prometheus/write", method="POST",
                       body=b"\xff\xfe\xfd",
                       headers={"Content-Encoding": "snappy"})
        assert code == 400


class TestReviewRegressions:
    def test_new_tag_added_online_not_dropped(self, server):
        # online tag addition (reference alter-on-demand): the second
        # write's new label column is ADDED; earlier rows read ""
        http(server, "/v1/influxdb/api/v2/write?precision=ms",
             method="POST", body=b"ttags,host=a v=1.0 1000")
        code, _raw = http(server, "/v1/influxdb/api/v2/write?precision=ms",
                          method="POST",
                          body=b"ttags,host=a,region=us v=2.0 2000")
        assert code == 204
        code, raw = http(server, "/v1/sql?" + urllib.parse.urlencode(
            {"sql": "SELECT host, region, v FROM ttags ORDER BY ts"}))
        rows = json.loads(raw)["output"][0]["records"]["rows"]
        assert rows == [["a", "", 1.0], ["a", "us", 2.0]]

    def test_bad_lp_timestamp_is_400(self, server):
        code, _ = http(server, "/v1/influxdb/write", method="POST",
                       body=b"cpu val=1 notanumber")
        assert code == 400

    def test_ns_timestamp_exact(self):
        ns = 1700000000123999999  # truncates to ...123 ms exactly
        out = parse_line_protocol(f"m v=1 {ns}")
        assert out["m"]["ts"] == [1700000000123]

    def test_snappy_overlapping_copy_fast(self):
        # run-length style: 1-byte literal + long overlapping copy
        data = b"a" * 10000
        assert snappy.decompress(snappy.compress(data)) == data
        import time
        big = bytes(np.random.default_rng(0).integers(65, 91, 2_000_000, dtype=np.uint8))
        t0 = time.time()
        assert snappy.decompress(snappy.compress(big)) == big
        assert time.time() - t0 < 2.0

    def test_partitioned_ingest_and_label_values(self, server):
        http(server, "/v1/sql", form={
            "sql": "CREATE TABLE ppt (host STRING, ts TIMESTAMP(3) TIME INDEX,"
                   " val DOUBLE, PRIMARY KEY (host))"
                   " PARTITION ON COLUMNS (host) (host < 'm', host >= 'm')"})
        lp = "ppt,host=alpha val=1 1000\nppt,host=zulu val=2 1000\n"
        code, _ = http(server, "/v1/influxdb/write?precision=ms",
                       method="POST", body=lp.encode())
        assert code == 204
        db = server.db
        info = db.catalog.get_table("public", "ppt")
        r1_hosts = set(db.regions.regions[info.region_ids[1]].scan_host()["host"])
        assert "zulu" in r1_hosts  # routed, not dumped into region 0
        code, raw = http(server, "/v1/prometheus/api/v1/label/host/values")
        vals = json.loads(raw)["data"]
        assert "alpha" in vals and "zulu" in vals


def _otlp_metrics_request():
    """Build a minimal ExportMetricsServiceRequest: one gauge + one histogram."""
    def kv(key, sval):
        anyv = _pb_len(1, sval.encode())
        return _pb_len(1, key.encode()) + _pb_len(2, anyv)

    def fixed64(field, val_bytes):
        return _pb_varint((field << 3) | 1) + val_bytes

    ts_ns = 1700000000 * 10**9
    # gauge point: attrs {pod=p1}, t, as_double 42.5
    pt = (_pb_len(7, kv("pod", "p1"))
          + fixed64(3, struct.pack("<Q", ts_ns))
          + fixed64(4, struct.pack("<d", 42.5)))
    gauge = _pb_len(1, pt)
    metric1 = _pb_len(1, b"cpu_usage") + _pb_len(5, gauge)
    # histogram point: count=6, sum=7.5, buckets [1,2,3] bounds [0.1, 1]
    hp = (_pb_len(9, kv("pod", "p1"))
          + fixed64(3, struct.pack("<Q", ts_ns))
          + fixed64(4, struct.pack("<Q", 6))
          + fixed64(5, struct.pack("<d", 7.5))
          + _pb_len(6, struct.pack("<QQQ", 1, 2, 3))
          + _pb_len(7, struct.pack("<dd", 0.1, 1.0)))
    hist = _pb_len(1, hp)
    metric2 = _pb_len(1, b"req_latency") + _pb_len(9, hist)
    scope_metrics = _pb_len(2, metric1) + _pb_len(2, metric2)
    resource = _pb_len(1, kv("svc", "api"))
    rm = _pb_len(1, resource) + _pb_len(2, scope_metrics)
    return _pb_len(1, rm)


class TestOtlpAndLoki:
    def test_otlp_metrics(self, server):
        body = _otlp_metrics_request()
        code, raw = http(server, "/v1/otlp/v1/metrics", method="POST", body=body,
                         headers={"Content-Type": "application/x-protobuf"})
        assert code == 200, raw
        code, raw = http(server, "/v1/sql?" + urllib.parse.urlencode(
            {"sql": "SELECT pod, svc, val FROM cpu_usage"}))
        rows = json.loads(raw)["output"][0]["records"]["rows"]
        assert rows == [["p1", "api", 42.5]]
        # histogram exploded prom-style with cumulative buckets
        code, raw = http(server, "/v1/sql?" + urllib.parse.urlencode(
            {"sql": "SELECT le, val FROM req_latency_bucket ORDER BY val"}))
        rows = json.loads(raw)["output"][0]["records"]["rows"]
        assert rows == [["0.1", 1.0], ["1.0", 3.0], ["+Inf", 6.0]]
        code, raw = http(server, "/v1/sql?" + urllib.parse.urlencode(
            {"sql": "SELECT val FROM req_latency_count"}))
        assert json.loads(raw)["output"][0]["records"]["rows"] == [[6.0]]
        # and histogram_quantile works over the bucket table
        code, raw = http(server, "/v1/prometheus/api/v1/query?" +
                         urllib.parse.urlencode({
                             "query": "histogram_quantile(0.5, req_latency_bucket)",
                             "time": str(1700000000 + 10)}))
        body = json.loads(raw)
        assert body["status"] == "success"
        assert len(body["data"]["result"]) == 1

    def test_loki_push_and_query(self, server):
        payload = {
            "streams": [{
                "stream": {"app": "web", "level": "error"},
                "values": [
                    ["1700000000000000000", "boom happened"],
                    ["1700000001000000000", "again"],
                ],
            }]
        }
        code, _ = http(server, "/v1/loki/api/v1/push", method="POST",
                       body=json.dumps(payload).encode(),
                       headers={"Content-Type": "application/json"})
        assert code == 204
        code, raw = http(server, "/v1/sql?" + urllib.parse.urlencode(
            {"sql": "SELECT app, level, line FROM loki_logs ORDER BY ts"}))
        rows = json.loads(raw)["output"][0]["records"]["rows"]
        assert rows == [["web", "error", "boom happened"],
                        ["web", "error", "again"]]

    def test_loki_protobuf_push(self, server):
        # promtail wire form: snappy(logproto.PushRequest)
        def varint(v):
            out = b""
            while True:
                b7 = v & 0x7F
                v >>= 7
                out += bytes([b7 | (0x80 if v else 0)])
                if not v:
                    return out

        def field(num, payload):
            return varint((num << 3) | 2) + varint(len(payload)) + payload

        # EntryAdapter: timestamp (field 1, message) + line (field 2)
        ts_msg = (varint(1 << 3 | 0) + varint(1700000099)
                  + varint(2 << 3 | 0) + varint(500_000_000))
        entry = field(1, ts_msg) + field(2, b"proto boom")
        stream = field(1, b'{job="api", env="prod"}') + field(2, entry)
        push = field(1, stream)
        code, _ = http(server, "/v1/loki/api/v1/push", method="POST",
                       body=snappy.compress(push),
                       headers={"Content-Type": "application/x-protobuf"})
        assert code == 204
        code, raw = http(server, "/v1/sql?" + urllib.parse.urlencode(
            {"sql": "SELECT job, env, line FROM loki_logs"
                    " WHERE job = 'api'"}))
        rows = json.loads(raw)["output"][0]["records"]["rows"]
        assert rows == [["api", "prod", "proto boom"]]

    def test_loki_bad_payload(self, server):
        code, _ = http(server, "/v1/loki/api/v1/push", method="POST",
                       body=b"not json",
                       headers={"Content-Type": "application/json"})
        assert code == 400

    def test_otel_arrow_metrics(self, server):
        import io

        import pyarrow as pa
        import pyarrow.ipc as pa_ipc

        tbl = pa.table({
            "name": ["otap_cpu", "otap_cpu", "otap_mem"],
            "time_unix_nano": [1700000000_000000000, 1700000001_000000000,
                               1700000000_000000000],
            "value": [0.5, 0.7, 1024.0],
            "host": ["h1", "h2", "h1"],
        })
        buf = io.BytesIO()
        with pa_ipc.new_stream(buf, tbl.schema) as w:
            w.write_table(tbl)
        code, raw = http(server, "/v1/otel-arrow/v1/metrics", method="POST",
                         body=buf.getvalue())
        assert code == 200 and json.loads(raw)["rows"] == 3
        code, raw = http(server, "/v1/sql?" + urllib.parse.urlencode(
            {"sql": "SELECT host, val FROM otap_cpu ORDER BY ts"}))
        rows = json.loads(raw)["output"][0]["records"]["rows"]
        assert [r[0] for r in rows] == ["h1", "h2"]
        assert rows[0][1] == pytest.approx(0.5)
        assert rows[1][1] == pytest.approx(0.7)  # f32 device storage
        code, raw = http(server, "/v1/sql?" + urllib.parse.urlencode(
            {"sql": "SELECT val FROM otap_mem"}))
        assert json.loads(raw)["output"][0]["records"]["rows"] == [[1024.0]]

    def test_otel_arrow_bad_body(self, server):
        code, _ = http(server, "/v1/otel-arrow/v1/metrics", method="POST",
                       body=b"not arrow")
        assert code == 400

    def test_loki_bad_entry_and_gzip(self, server):
        payload = {"streams": [{"stream": {"a": "b"},
                                "values": [["not-a-number", "line"]]}]}
        code, _ = http(server, "/v1/loki/api/v1/push", method="POST",
                       body=json.dumps(payload).encode(),
                       headers={"Content-Type": "application/json"})
        assert code == 400
        code, _ = http(server, "/v1/otlp/v1/metrics", method="POST",
                       body=b"\x1f\x8b truncated",
                       headers={"Content-Encoding": "gzip"})
        assert code == 400

    def test_reserved_label_names(self):
        # loki labels named ts/line must not corrupt the batch (fresh db:
        # loki_logs schema is created from the first batch's labels)
        db = GreptimeDB()
        srv = HttpServer(db, port=0)
        srv.start()
        try:
            payload = {"streams": [{"stream": {"ts": "oops", "line": "also"},
                                    "values": [["1700000000000000000", "msg"]]}]}
            code, _ = http(srv, "/v1/loki/api/v1/push", method="POST",
                           body=json.dumps(payload).encode(),
                           headers={"Content-Type": "application/json"})
            assert code == 204
            code, raw = http(srv, "/v1/sql?" + urllib.parse.urlencode(
                {"sql": "SELECT ts_label, line_label, line FROM loki_logs"
                        " WHERE ts_label = 'oops'"}))
            rows = json.loads(raw)["output"][0]["records"]["rows"]
            assert rows == [["oops", "also", "msg"]]
        finally:
            srv.stop()
            db.close()


class TestMoreProtocols:
    def test_opentsdb_put(self, server):
        pts = [{"metric": "sys_cpu", "timestamp": 1700000000,
                "value": 42.5, "tags": {"host": "web01"}},
               {"metric": "sys_cpu", "timestamp": 1700000010,
                "value": 43.0, "tags": {"host": "web01"}}]
        code, _ = http(server, "/v1/opentsdb/api/put", method="POST",
                       body=json.dumps(pts).encode())
        assert code == 204
        code, raw = http(server, "/v1/sql?" + urllib.parse.urlencode(
            {"sql": "SELECT host, val FROM sys_cpu ORDER BY ts"}))
        rows = json.loads(raw)["output"][0]["records"]["rows"]
        assert rows == [["web01", 42.5], ["web01", 43.0]]
        code, _ = http(server, "/v1/opentsdb/api/put", method="POST",
                       body=b"[{\"nope\": 1}]")
        assert code == 400

    def test_es_bulk(self, server):
        nd = (
            '{"index": {"_index": "app-logs"}}\n'
            '{"@timestamp": "2026-01-01T00:00:00Z", "message": "hello"}\n'
            '{"create": {"_index": "app-logs"}}\n'
            '{"@timestamp": "2026-01-01T00:00:01Z", "message": "world"}\n'
        )
        code, raw = http(server, "/v1/elasticsearch/_bulk", method="POST",
                         body=nd.encode())
        assert code == 200 and json.loads(raw)["errors"] is False
        code, raw = http(server, "/v1/sql?" + urllib.parse.urlencode(
            {"sql": "SELECT doc FROM app_logs ORDER BY ts"}))
        rows = json.loads(raw)["output"][0]["records"]["rows"]
        assert len(rows) == 2 and "hello" in rows[0][0]
        code, raw = http(server, "/v1/elasticsearch/")
        assert json.loads(raw)["version"]["number"].startswith("8.")

    def test_splunk_hec(self, server):
        events = (
            '{"time": 1700000000.5, "sourcetype": "access",'
            ' "event": "GET /"}'
            '{"time": 1700000001, "sourcetype": "access",'
            ' "event": {"msg": "structured"}}'
        )
        code, raw = http(server, "/v1/splunk/services/collector",
                         method="POST", body=events.encode())
        assert code == 200 and json.loads(raw)["code"] == 0
        code, raw = http(server, "/v1/sql?" + urllib.parse.urlencode(
            {"sql": "SELECT sourcetype, event FROM splunk_events ORDER BY ts"}))
        rows = json.loads(raw)["output"][0]["records"]["rows"]
        assert rows[0] == ["access", "GET /"]
        assert "structured" in rows[1][1]

    def test_opentsdb_reserved_tag_and_bad_ts(self, server):
        pts = [{"metric": "rm1", "timestamp": 1700000000, "value": 1.0,
                "tags": {"ts": "x", "val": "y"}}]
        code, _ = http(server, "/v1/opentsdb/api/put", method="POST",
                       body=json.dumps(pts).encode())
        assert code == 204
        code, raw = http(server, "/v1/sql?" + urllib.parse.urlencode(
            {"sql": "SELECT ts_tag, val_tag, val FROM rm1"}))
        rows = json.loads(raw)["output"][0]["records"]["rows"]
        assert rows == [["x", "y", 1.0]]
        code, _ = http(server, "/v1/opentsdb/api/put", method="POST",
                       body=b'{"metric":"m","timestamp":"abc","value":1}')
        assert code == 400

    def test_es_bulk_desync_recovery(self, server):
        nd = ('{"index": {"_index": "dsync"}}\n'
              'not json at all {{{\n'
              '{"index": {"_index": "dsync"}}\n'
              '{"@timestamp": "2026-01-01T00:00:00Z", "message": "real"}\n')
        code, _ = http(server, "/v1/elasticsearch/_bulk", method="POST",
                       body=nd.encode())
        assert code == 200
        code, raw = http(server, "/v1/sql?" + urllib.parse.urlencode(
            {"sql": "SELECT doc FROM dsync"}))
        rows = json.loads(raw)["output"][0]["records"]["rows"]
        assert len(rows) == 1 and "real" in rows[0][0]

    def test_splunk_bad_payload(self, server):
        code, _ = http(server, "/v1/splunk/services/collector",
                       method="POST", body=b'{"time":1} {{{garbage')
        assert code == 400


def _otlp_traces_request():
    """ExportTraceServiceRequest: 2 spans in one trace + 1 in another."""
    def kv(key, sval):
        return _pb_len(1, key.encode()) + _pb_len(2, _pb_len(1, sval.encode()))

    def fixed64(field, value):
        return _pb_varint((field << 3) | 1) + struct.pack("<Q", value)

    t0 = 1700000000 * 10**9

    def span(tid, sid, parent, name, start, dur, kind=2):
        s = (_pb_len(1, bytes.fromhex(tid)) + _pb_len(2, bytes.fromhex(sid))
             + (_pb_len(4, bytes.fromhex(parent)) if parent else b"")
             + _pb_len(5, name.encode())
             + _pb_varint(6 << 3) + _pb_varint(kind)
             + fixed64(7, start) + fixed64(8, start + dur)
             + _pb_len(9, kv("http.method", "GET")))
        return _pb_len(2, s)

    tid1 = "0102030405060708090a0b0c0d0e0f10"
    tid2 = "1112131415161718191a1b1c1d1e1f20"
    spans = (span(tid1, "0102030405060708", "", "GET /api", t0, 50_000_000)
             + span(tid1, "1112131415161718", "0102030405060708", "db.query",
                    t0 + 10**7, 20_000_000, kind=3)
             + span(tid2, "2122232425262728", "", "GET /other", t0 + 10**9,
                    5_000_000))
    # ScopeSpans message = concatenated field-2 Span entries; ResourceSpans
    # wraps it once as ITS field 2
    resource = _pb_len(1, kv("service.name", "api-server"))
    rs = _pb_len(1, resource) + _pb_len(2, spans)
    return _pb_len(1, rs), tid1, tid2


class TestTraces:
    def test_otlp_traces_and_jaeger_api(self, server):
        body, tid1, tid2 = _otlp_traces_request()
        code, raw = http(server, "/v1/otlp/v1/traces", method="POST", body=body)
        assert code == 200, raw
        # services
        code, raw = http(server, "/v1/jaeger/api/services")
        assert "api-server" in json.loads(raw)["data"]
        # operations
        code, raw = http(server, "/v1/jaeger/api/operations?service=api-server")
        names = {o["name"] for o in json.loads(raw)["data"]}
        assert {"GET /api", "db.query", "GET /other"} <= names
        # get one trace
        code, raw = http(server, f"/v1/jaeger/api/traces/{tid1}")
        assert code == 200
        data = json.loads(raw)["data"]
        assert len(data) == 1 and len(data[0]["spans"]) == 2
        span = next(s for s in data[0]["spans"] if s["operationName"] == "GET /api")
        assert span["duration"] == 50_000
        child = next(s for s in data[0]["spans"] if s["operationName"] == "db.query")
        assert child["references"][0]["spanID"] == "0102030405060708"
        # search with filters
        q = urllib.parse.urlencode({"service": "api-server",
                                    "operation": "GET /other"})
        code, raw = http(server, f"/v1/jaeger/api/traces?{q}")
        data = json.loads(raw)["data"]
        assert [t["traceID"] for t in data] == [tid2]
        # min duration filter excludes the short trace
        q = urllib.parse.urlencode({"service": "api-server",
                                    "minDuration": "40000us"})
        code, raw = http(server, f"/v1/jaeger/api/traces?{q}")
        assert [t["traceID"] for t in json.loads(raw)["data"]] == [tid1]
        # unknown trace -> 404
        code, _ = http(server, "/v1/jaeger/api/traces/" + "00" * 16)
        assert code == 404
        # spans also queryable via plain SQL
        code, raw = http(server, "/v1/sql?" + urllib.parse.urlencode(
            {"sql": "SELECT count(*) FROM opentelemetry_traces"}))
        assert json.loads(raw)["output"][0]["records"]["rows"] == [[3]]

    def test_go_duration_units(self):
        from greptimedb_tpu.servers.http import _parse_go_duration_us

        assert _parse_go_duration_us("50us") == 50
        assert _parse_go_duration_us("100ms") == 100_000
        assert _parse_go_duration_us("2s") == 2_000_000
        assert _parse_go_duration_us("1m") == 60_000_000
        assert _parse_go_duration_us("250") == 250

    def test_multi_service_trace_processes(self):
        from greptimedb_tpu.servers.trace import _traces_payload

        spans = [
            {"service_name": "web", "trace_id": "t1", "span_id": "a",
             "parent_span_id": "", "span_name": "GET /", "span_kind":
             "SPAN_KIND_SERVER", "ts": 1, "duration_nano": 1000,
             "status_code": "STATUS_CODE_OK", "attributes": "{}"},
            {"service_name": "auth", "trace_id": "t1", "span_id": "b",
             "parent_span_id": "a", "span_name": "check", "span_kind":
             "SPAN_KIND_CLIENT", "ts": 2, "duration_nano": 500,
             "status_code": "STATUS_CODE_OK", "attributes": "{}"},
        ]
        out = _traces_payload({"t1": spans})
        procs = out[0]["processes"]
        by_op = {s["operationName"]: s["processID"] for s in out[0]["spans"]}
        assert procs[by_op["GET /"]]["serviceName"] == "web"
        assert procs[by_op["check"]]["serviceName"] == "auth"


class TestLogQueryApi:
    def test_log_query_dsl(self):
        db = GreptimeDB()
        srv = HttpServer(db, port=0)
        srv.start()
        try:
            payload = {"streams": [{
                "stream": {"app": "web"},
                "values": [
                    ["1700000000000000000", "GET /index ok"],
                    ["1700000001000000000", "error: boom"],
                    ["1700000002000000000", "GET /health ok"],
                ]}]}
            http(srv, "/v1/loki/api/v1/push", method="POST",
                 body=json.dumps(payload).encode(),
                 headers={"Content-Type": "application/json"})
            q = {
                "table": {"schema": "public", "table": "loki_logs"},
                "filters": [{"column": "line",
                             "filters": [{"contains": "error"}]}],
                "columns": ["ts", "line"],
                "limit": {"fetch": 10},
            }
            code, raw = http(srv, "/v1/logs", method="POST",
                             body=json.dumps(q).encode())
            assert code == 200, raw
            rec = json.loads(raw)["output"][0]["records"]
            assert rec["rows"] == [[1700000001000, "error: boom"]]
            # prefix + newest-first ordering + limit
            q2 = {"table": {"table": "loki_logs"},
                  "filters": [{"column": "line",
                               "filters": [{"prefix": "GET"}]}],
                  "columns": ["line"], "limit": {"fetch": 1}}
            code, raw = http(srv, "/v1/logs", method="POST",
                             body=json.dumps(q2).encode())
            rows = json.loads(raw)["output"][0]["records"]["rows"]
            assert rows == [["GET /health ok"]]
            # bad column -> 400
            q3 = {"table": {"table": "loki_logs"},
                  "filters": [{"column": "nope", "filters": [{"eq": "x"}]}]}
            code, _ = http(srv, "/v1/logs", method="POST",
                           body=json.dumps(q3).encode())
            assert code == 400
        finally:
            srv.stop()
            db.close()

    def test_log_query_empty_and_malformed(self):
        db = GreptimeDB()
        srv = HttpServer(db, port=0)
        srv.start()
        try:
            db.sql("CREATE TABLE el (app STRING, ts TIMESTAMP(3) TIME INDEX,"
                   " line STRING, PRIMARY KEY (app))")
            # empty table + contains filter: zero rows, not a 500
            q = {"table": {"table": "el"},
                 "filters": [{"column": "line",
                              "filters": [{"contains": "x"}]}]}
            code, raw = http(srv, "/v1/logs", method="POST",
                             body=json.dumps(q).encode())
            assert code == 200, raw
            assert json.loads(raw)["output"][0]["records"]["rows"] == []
            # bad regex -> 400
            q["filters"][0]["filters"] = [{"regex": "("}]
            code, _ = http(srv, "/v1/logs", method="POST",
                           body=json.dumps(q).encode())
            assert code == 400
            # non-object body -> 400
            code, _ = http(srv, "/v1/logs", method="POST", body=b"[1, 2]")
            assert code == 400
        finally:
            srv.stop()
            db.close()



class TestDebugEndpoints:
    def test_dyn_log_level_and_prof(self):
        import json as _json
        import urllib.request

        from greptimedb_tpu.servers.http import HttpServer
        from greptimedb_tpu.standalone import GreptimeDB

        db = GreptimeDB()
        srv = HttpServer(db, host="127.0.0.1", port=0)
        srv.start()
        base = f"http://127.0.0.1:{srv.port}"
        try:
            out = _json.loads(urllib.request.urlopen(
                base + "/debug/log_level").read())
            assert "level" in out
            req = urllib.request.Request(
                base + "/debug/log_level", data=b"debug", method="POST")
            out = _json.loads(urllib.request.urlopen(req).read())
            assert out["level"] == "DEBUG"
            req = urllib.request.Request(
                base + "/debug/log_level", data=b"warning", method="POST")
            assert _json.loads(urllib.request.urlopen(req).read())[
                "level"] == "WARNING"
            prof = urllib.request.urlopen(
                base + "/debug/prof/cpu?seconds=0.3").read().decode()
            assert prof.startswith("samples=")
        finally:
            srv.stop()
            db.close()


class TestExternalTables:
    def test_external_parquet_and_csv(self, tmp_path):
        import pyarrow as pa
        import pyarrow.parquet as pq

        from greptimedb_tpu.standalone import GreptimeDB

        t = pa.table({"host": ["a", "b", "a"],
                      "ts": pa.array([1000, 2000, 3000], pa.timestamp("ms")),
                      "v": [1.0, 2.0, 3.0]})
        pq.write_table(t, str(tmp_path / "p1.parquet"))
        (tmp_path / "c.csv").write_text("host,ts,v\na,1000,5.0\nc,4000,7.0\n")
        db = GreptimeDB()
        try:
            db.sql(f"CREATE EXTERNAL TABLE extp (host STRING, ts "
                   f"TIMESTAMP(3) TIME INDEX, v DOUBLE, PRIMARY KEY (host)) "
                   f"WITH (location='{tmp_path}/p1.parquet', "
                   f"format='parquet')")
            assert db.sql("SELECT host, sum(v) FROM extp GROUP BY host "
                          "ORDER BY host").rows == [["a", 4.0], ["b", 2.0]]
            db.sql(f"CREATE EXTERNAL TABLE extc (host STRING, ts "
                   f"TIMESTAMP(3) TIME INDEX, v DOUBLE, PRIMARY KEY (host)) "
                   f"WITH (location='{tmp_path}/c.csv', format='csv')")
            assert db.sql("SELECT count(*), max(v) FROM extc"
                          ).rows == [[2, 7.0]]
            from greptimedb_tpu.errors import Unsupported

            with pytest.raises(Unsupported):
                db.sql("INSERT INTO extp VALUES ('x', 9000, 1.0)")
            # joins between native and external tables work
            db.sql("CREATE TABLE nat (host STRING, ts TIMESTAMP(3) "
                   "TIME INDEX, w DOUBLE, PRIMARY KEY (host))")
            db.sql("INSERT INTO nat VALUES ('a', 0, 10.0)")
            r = db.sql("SELECT n.host, sum(e.v * n.w) FROM nat n "
                       "JOIN extp e ON n.host = e.host GROUP BY n.host")
            assert r.rows == [["a", 40.0]]
        finally:
            db.close()


class TestGcAndMetaSnapshot:
    def test_gc_deletes_orphans(self, tmp_path):
        import os
        import time

        from greptimedb_tpu.standalone import GreptimeDB

        db = GreptimeDB(str(tmp_path / "home"))
        try:
            db.sql("CREATE TABLE g (h STRING, ts TIMESTAMP(3) TIME INDEX, "
                   "v DOUBLE, PRIMARY KEY (h))")
            db.sql("INSERT INTO g VALUES ('a', 1000, 1.0)")
            r = db._region_of("g")
            r.flush()
            rid = r.region_id
            # plant an orphan object (failed flush leftover)
            orphan = f"region_{rid}/sst/deadbeef.parquet"
            db.regions.store.write(orphan, b"junk")
            lp = db.regions.store.local_path(orphan)
            old = time.time() - 7200
            os.utime(lp, (old, old))
            deleted = db.regions.gc(grace_seconds=3600)
            assert orphan in deleted
            # live SSTs untouched
            assert db.sql("SELECT count(*) FROM g").rows == [[1]]
        finally:
            db.close()

    def test_meta_snapshot_restore(self, tmp_path):
        from greptimedb_tpu.cli import main as cli_main
        from greptimedb_tpu.standalone import GreptimeDB

        home = str(tmp_path / "home")
        db = GreptimeDB(home)
        db.sql("CREATE TABLE ms (h STRING, ts TIMESTAMP(3) TIME INDEX, "
               "v DOUBLE, PRIMARY KEY (h))")
        db.close()
        snap = str(tmp_path / "meta.json")
        assert cli_main(["meta", "snapshot", "--data-home", home,
                         "--file", snap]) == 0
        home2 = str(tmp_path / "home2")
        assert cli_main(["meta", "restore", "--data-home", home2,
                         "--file", snap]) == 0
        db2 = GreptimeDB(home2)
        try:
            # table metadata restored (no data: that's export/import's job)
            assert db2.sql("SHOW TABLES").rows == [["ms"]]
        finally:
            db2.close()

    def test_recreated_external_table_not_stale(self, tmp_path):
        import pyarrow as pa
        import pyarrow.parquet as pq

        from greptimedb_tpu.standalone import GreptimeDB

        pq.write_table(pa.table({"host": ["old"], "ts": pa.array(
            [1000], pa.timestamp("ms")), "v": [1.0]}),
            str(tmp_path / "a.parquet"))
        pq.write_table(pa.table({"host": ["new"], "ts": pa.array(
            [2000], pa.timestamp("ms")), "v": [2.0]}),
            str(tmp_path / "b.parquet"))
        db = GreptimeDB()
        try:
            ddl = ("CREATE EXTERNAL TABLE e (host STRING, ts TIMESTAMP(3) "
                   "TIME INDEX, v DOUBLE, PRIMARY KEY (host)) "
                   "WITH (location='{}', format='parquet')")
            db.sql(ddl.format(tmp_path / "a.parquet"))
            assert db.sql("SELECT host FROM e").rows == [["old"]]
            db.sql("DROP TABLE e")
            db.sql(ddl.format(tmp_path / "b.parquet"))
            assert db.sql("SELECT host FROM e").rows == [["new"]]
        finally:
            db.close()

    def test_join_star_hides_joinrow(self):
        from greptimedb_tpu.standalone import GreptimeDB

        db = GreptimeDB()
        try:
            db.sql("CREATE TABLE a (h STRING, ts TIMESTAMP(3) TIME INDEX, "
                   "v DOUBLE, PRIMARY KEY (h))")
            db.sql("CREATE TABLE b (h STRING, ts TIMESTAMP(3) TIME INDEX, "
                   "w DOUBLE, PRIMARY KEY (h))")
            db.sql("INSERT INTO a VALUES ('x', 1000, 1.0)")
            db.sql("INSERT INTO b VALUES ('x', 2000, 2.0)")
            r = db.sql("SELECT * FROM a JOIN b ON a.h = b.h")
            assert "__joinrow__" not in r.column_names
        finally:
            db.close()


def _decode_read_response(raw: bytes) -> list[list[tuple[dict, list]]]:
    from greptimedb_tpu.servers.protocols import _pb_fields

    results = []
    for f, _wt, qr in _pb_fields(raw):
        if f != 1:
            continue
        series = []
        for f2, _wt2, ts_msg in _pb_fields(qr):
            if f2 != 1:
                continue
            labels, samples = {}, []
            for f3, _wt3, v3 in _pb_fields(ts_msg):
                if f3 == 1:
                    name = value = ""
                    for f4, _wt4, v4 in _pb_fields(v3):
                        if f4 == 1:
                            name = v4.decode()
                        elif f4 == 2:
                            value = v4.decode()
                    labels[name] = value
                elif f3 == 2:
                    val, ts = 0.0, 0
                    for f4, wt4, v4 in _pb_fields(v3):
                        if f4 == 1:
                            val = struct.unpack("<d", v4)[0]
                        elif f4 == 2:
                            ts = v4
                    samples.append((val, ts))
            series.append((labels, samples))
        results.append(series)
    return results


class TestPromRemoteRead:
    @staticmethod
    def _write_rr_metric(server):
        """Both tests read these samples; each writes them itself (the
        same samples again change nothing), so neither needs the other to
        have run on its worker."""
        ts0 = 1700001000000
        pb = make_write_request([
            ({"__name__": "rr_metric", "job": "api", "inst": "a"},
             [(1.5, ts0), (2.5, ts0 + 10_000)]),
            ({"__name__": "rr_metric", "job": "web", "inst": "b"},
             [(9.0, ts0 + 5_000)]),
        ])
        code, _ = http(server, "/v1/prometheus/write", method="POST",
                       body=snappy.compress(pb),
                       headers={"Content-Encoding": "snappy"})
        assert code == 204
        return ts0

    def test_write_then_remote_read(self, server):
        ts0 = self._write_rr_metric(server)
        # ReadRequest{queries=1:{start=1,end=2,matchers=3:{type=1,name=2,value=3}}}
        def matcher(mtype, name, value):
            m = b""
            if mtype:
                m += _pb_varint(1 << 3) + _pb_varint(mtype)
            m += _pb_len(2, name.encode()) + _pb_len(3, value.encode())
            return _pb_len(3, m)

        q = (_pb_varint(1 << 3) + _pb_varint(ts0 & ((1 << 64) - 1))
             + _pb_varint(2 << 3) + _pb_varint((ts0 + 60_000) & ((1 << 64) - 1))
             + matcher(0, "__name__", "rr_metric")
             + matcher(0, "job", "api"))
        req = _pb_len(1, q)
        code, raw = http(server, "/v1/prometheus/read", method="POST",
                         body=snappy.compress(req),
                         headers={"Content-Encoding": "snappy"})
        assert code == 200, raw
        results = _decode_read_response(snappy.decompress(raw))
        assert len(results) == 1
        series = results[0]
        assert len(series) == 1
        labels, samples = series[0]
        assert labels["__name__"] == "rr_metric"
        assert labels["job"] == "api" and labels["inst"] == "a"
        assert samples == [(1.5, ts0), (2.5, ts0 + 10_000)]

    def test_regex_matcher_and_missing_metric(self, server):
        def matcher(mtype, name, value):
            m = b""
            if mtype:
                m += _pb_varint(1 << 3) + _pb_varint(mtype)
            m += _pb_len(2, name.encode()) + _pb_len(3, value.encode())
            return _pb_len(3, m)

        ts0 = self._write_rr_metric(server)
        q = (_pb_varint(1 << 3) + _pb_varint(0)
             + _pb_varint(2 << 3) + _pb_varint((ts0 + 60_000))
             + matcher(0, "__name__", "rr_metric")
             + matcher(2, "job", "a.*|w.*"))
        code, raw = http(server, "/v1/prometheus/read", method="POST",
                         body=snappy.compress(_pb_len(1, q)),
                         headers={"Content-Encoding": "snappy"})
        assert code == 200
        got = _decode_read_response(snappy.decompress(raw))
        assert len(got[0]) == 2  # both series match the regex
        # unknown metric -> empty result, not an error
        q2 = (_pb_varint(1 << 3) + _pb_varint(0)
              + _pb_varint(2 << 3) + _pb_varint(ts0)
              + matcher(0, "__name__", "nope"))
        code, raw = http(server, "/v1/prometheus/read", method="POST",
                         body=snappy.compress(_pb_len(1, q2)),
                         headers={"Content-Encoding": "snappy"})
        assert code == 200
        assert _decode_read_response(snappy.decompress(raw)) == [[]]


def make_otlp_logs(records: list[dict]) -> bytes:
    """Build an ExportLogsServiceRequest from simple record dicts."""
    def any_str(s):
        return _pb_len(1, s.encode())

    def kv(k, v):
        return _pb_len(1, k.encode()) + _pb_len(2, any_str(v))

    recs = b""
    for r in records:
        body = b""
        body += _pb_varint((1 << 3) | 1) + struct.pack(
            "<Q", r["ts_ns"])  # time_unix_nano fixed64
        body += _pb_varint(2 << 3) + _pb_varint(r.get("severity_number", 9))
        body += _pb_len(3, r.get("severity_text", "INFO").encode())
        body += _pb_len(5, any_str(r["body"]))
        for k, v in r.get("attrs", {}).items():
            body += _pb_len(6, kv(k, v))
        if r.get("trace_id"):
            body += _pb_len(9, bytes.fromhex(r["trace_id"]))
        recs += _pb_len(2, body)
    scope = _pb_len(1, _pb_len(1, b"my-lib") + _pb_len(2, b"1.2.3"))
    scope_logs = _pb_len(2, scope + recs)
    resource = _pb_len(1, _pb_len(1, kv("service.name", "checkout")))
    return _pb_len(1, resource + scope_logs)


class TestOtlpLogs:
    def test_ingest_and_query(self, server):
        payload = make_otlp_logs([
            {"ts_ns": 1700000001000 * 10**6, "body": "user login ok",
             "attrs": {"user": "alice"}, "trace_id": "ab" * 16},
            {"ts_ns": 1700000002000 * 10**6, "body": "payment failed",
             "severity_text": "ERROR", "severity_number": 17},
        ])
        code, raw = http(server, "/v1/otlp/v1/logs", method="POST",
                         body=payload)
        assert code == 200, raw
        q = urllib.parse.urlencode({
            "sql": "SELECT severity_text, body, trace_id, "
                   "resource_attributes FROM opentelemetry_logs ORDER BY ts"})
        code, raw = http(server, f"/v1/sql?{q}")
        rows = json.loads(raw)["output"][0]["records"]["rows"]
        assert len(rows) == 2
        assert rows[0][1] == "user login ok" and rows[0][2] == "ab" * 16
        assert rows[1][0] == "ERROR"
        assert json.loads(rows[0][3]) == {"service.name": "checkout"}

    def test_custom_table_header(self, server):
        payload = make_otlp_logs([
            {"ts_ns": 1700000003000 * 10**6, "body": "x"}])
        code, _ = http(server, "/v1/otlp/v1/logs", method="POST",
                       body=payload,
                       headers={"x-greptime-log-table-name": "applogs"})
        assert code == 200
        q = urllib.parse.urlencode({"sql": "SELECT count(*) FROM applogs"})
        code, raw = http(server, f"/v1/sql?{q}")
        assert json.loads(raw)["output"][0]["records"]["rows"] == [[1]]
