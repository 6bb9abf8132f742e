"""Self-monitoring loop: trace-context propagation + loopback span/metric
self-export into the instance's own tables.

Reference counterparts: W3C traceparent handling + x-greptime-trace-id
(src/servers/src/http/header.rs), Jaeger query API over
opentelemetry_traces (src/servers/src/http/jaeger.rs), and the
standalone's ``export_metrics`` self_import timer (SURVEY.md §5.5).
"""

import json
import sys
import time
import urllib.parse
import urllib.request

import pytest

from greptimedb_tpu.standalone import GreptimeDB
from greptimedb_tpu.utils.selfmonitor import SelfMonitor
from greptimedb_tpu.utils.tracing import (
    TRACER, extract_sql_trace_context, parse_trace_id, parse_traceparent,
)

TID = "0123456789abcdef0123456789abcdef"
PSPAN = "00f067aa0ba902b7"
TP = f"00-{TID}-{PSPAN}-01"


@pytest.fixture
def db():
    d = GreptimeDB()
    d.sql("CREATE TABLE cpu (h STRING, ts TIMESTAMP(3) TIME INDEX, "
          "v DOUBLE, PRIMARY KEY (h))")
    d.sql("INSERT INTO cpu VALUES ('a', 1000, 1.0), ('b', 2000, 2.0), "
          "('a', 3000, 3.0), ('b', 4000, 4.0)")
    yield d
    d.close()


@pytest.fixture
def traced():
    TRACER.configure(enabled=True)
    TRACER.drain()
    yield TRACER
    TRACER.disable()


# ---------------------------------------------------------------------------
# traceparent / x-greptime-trace-id parsing (malformed values are ignored)
# ---------------------------------------------------------------------------

class TestTraceContextParsing:
    def test_valid_traceparent(self):
        assert parse_traceparent(TP) == (TID, PSPAN)

    def test_uppercase_hex_lowercased(self):
        up = f"00-{TID.upper()}-{PSPAN.upper()}-01"
        assert parse_traceparent(up) == (TID, PSPAN)

    def test_surrounding_whitespace(self):
        assert parse_traceparent(f"  {TP}\n") == (TID, PSPAN)

    @pytest.mark.parametrize("bad", [
        None,
        "",
        "00",                                   # too few members
        f"00-{TID}-{PSPAN}",                    # missing flags
        f"0-{TID}-{PSPAN}-01",                  # short version
        f"ff-{TID}-{PSPAN}-01",                 # forbidden version
        f"zz-{TID}-{PSPAN}-01",                 # non-hex version
        f"00-{TID[:-2]}-{PSPAN}-01",            # short trace id
        f"00-{TID}xx-{PSPAN}-01",               # long/non-hex trace id
        f"00-{'0' * 32}-{PSPAN}-01",            # all-zero trace id
        f"00-{TID}-{'0' * 16}-01",              # all-zero span id
        f"00-{TID}-{PSPAN[:-1]}-01",            # short span id
        f"00-{TID}-{PSPAN}-0g",                 # non-hex flags
        f"00-{TID}-{PSPAN}-01-extra",           # version 00 forbids members
    ])
    def test_malformed_is_ignored(self, bad):
        assert parse_traceparent(bad) is None

    def test_future_version_extra_members_accepted(self):
        assert parse_traceparent(f"cc-{TID}-{PSPAN}-01-what") == (TID, PSPAN)

    def test_trace_id_header(self):
        assert parse_trace_id(TID) == (TID, "")
        assert parse_trace_id(TID.upper()) == (TID, "")
        assert parse_trace_id("abc") is None
        assert parse_trace_id("0" * 32) is None
        assert parse_trace_id(None) is None

    def test_sql_comment_extraction(self):
        assert extract_sql_trace_context(
            f"/* traceparent='{TP}' */ SELECT 1") == (TID, PSPAN)
        assert extract_sql_trace_context(
            f"-- traceparent='{TP}'\nSELECT 1") == (TID, PSPAN)
        assert extract_sql_trace_context(
            f"/* retry */ /* traceparent='{TP}' */ SELECT 1") == (TID, PSPAN)
        assert extract_sql_trace_context("SELECT 1") is None
        assert extract_sql_trace_context(
            "/* traceparent='00-garbage-x-01' */ SELECT 1") is None

    def test_sql_literal_never_seeds_context(self):
        # only LEADING comments count: a traceparent-looking substring
        # inside user data must not hijack trace correlation
        assert extract_sql_trace_context(
            f"SELECT * FROM logs WHERE msg = \"saw traceparent='{TP}'\""
        ) is None
        assert extract_sql_trace_context(
            f"INSERT INTO t VALUES ('traceparent=''{TP}''', 1)") is None


# ---------------------------------------------------------------------------
# Propagation: span trees seeded with the external id; slow_queries +
# EXPLAIN ANALYZE carry it
# ---------------------------------------------------------------------------

class TestPropagation:
    def test_span_tree_seeded_with_external_trace_id(self, db, traced):
        with TRACER.trace_context((TID, PSPAN)):
            db.sql("SELECT h, avg(v) FROM cpu GROUP BY h")
        spans = TRACER.drain()
        assert spans
        assert all(s["trace_id"] == TID for s in spans)
        # the top-level stages (parse + the statement root "sql") are
        # children of the CLIENT's span, not orphans
        roots = {s["name"] for s in spans if s["parent_span_id"] == PSPAN}
        assert roots == {"parse", "sql"}

    def test_wire_comment_propagation_via_tcp_entry(self, db, traced):
        from greptimedb_tpu.servers.tcp import ThreadedTcpServer

        srv = ThreadedTcpServer(db, "127.0.0.1", 0)
        res, _db, _tz = srv.timed_sql_in_db(
            f"/* traceparent='{TP}' */ SELECT h, avg(v) FROM cpu GROUP BY h",
            "public")
        assert res.rows
        spans = TRACER.drain()
        assert spans and all(s["trace_id"] == TID for s in spans)
        srv._db_executor.shutdown(wait=False)

    def test_slow_query_trace_id_column(self, db, traced):
        db.slow_query_threshold_ms = 0.0001
        try:
            with TRACER.trace_context((TID, PSPAN)):
                db.sql("SELECT h, avg(v) FROM cpu GROUP BY h")
        finally:
            db.slow_query_threshold_ms = 0.0
        r = db.sql("SELECT query, trace_id FROM "
                   "greptime_private.slow_queries")
        by_query = dict(r.rows)
        assert by_query["SELECT h, avg(v) FROM cpu GROUP BY h"] == TID

    def test_slow_query_trace_id_without_tracer(self, db):
        # the trace id rides the thread-local even with the tracer off:
        # a client-supplied traceparent still tags the slow-query record
        assert not TRACER.enabled
        db.slow_query_threshold_ms = 0.0001
        try:
            with TRACER.trace_context((TID, "")):
                db.sql("SELECT h FROM cpu")
        finally:
            db.slow_query_threshold_ms = 0.0
        r = db.sql("SELECT trace_id FROM greptime_private.slow_queries")
        assert [TID] in r.rows

    def test_explain_analyze_trace_id_row(self, db, traced):
        r = db.sql("EXPLAIN ANALYZE SELECT h, avg(v) FROM cpu GROUP BY h")
        labels = [row[0] for row in r.rows]
        assert "analyze (trace_id)" in labels
        tid = r.rows[labels.index("analyze (trace_id)")][1]
        assert len(tid) == 32 and all(c in "0123456789abcdef" for c in tid)


# ---------------------------------------------------------------------------
# Loopback export: spans → opentelemetry_traces (Jaeger-visible), registry
# → metric tables (PromQL-visible)
# ---------------------------------------------------------------------------

class TestSelfExport:
    def test_span_loopback_retrievable_via_jaeger(self, db, traced):
        with TRACER.trace_context((TID, PSPAN)):
            db.sql("SELECT h, avg(v) FROM cpu GROUP BY h")
        mon = SelfMonitor(db)
        assert mon.flush_spans() > 0
        from greptimedb_tpu.servers.trace import jaeger_services, jaeger_trace

        assert TRACER.service_name in jaeger_services(db)
        data = jaeger_trace(db, TID)
        assert data and data[0]["traceID"] == TID
        ops = {s["operationName"] for s in data[0]["spans"]}
        assert {"sql", "execute_statement", "parse", "optimize", "plan",
                "execute", "materialize"} <= ops

    def test_metrics_self_import_promql(self, db):
        db.sql("SELECT h, avg(v) FROM cpu GROUP BY h")  # bump counters
        mon = SelfMonitor(db)
        assert mon.export_metrics() > 0
        now = int(time.time())
        r = db.sql(f"TQL EVAL ({now - 60}, {now + 60}, '30s') "
                   "greptime_query_duration_seconds_count")
        assert r.rows, "self-imported counter returned no samples"
        # the histogram exploded prometheus-style: _bucket carries an le tag
        r = db.sql("SELECT count(*) FROM "
                   "greptime_query_duration_seconds_bucket WHERE le = '+Inf'")
        assert r.rows[0][0] > 0

    def test_failed_flush_requeues_spans(self, db, traced, monkeypatch):
        # a write failure must not lose drained spans: they requeue and
        # the next (healthy) tick exports them
        with TRACER.trace_context((TID, PSPAN)):
            db.sql("SELECT h FROM cpu")
        n_buffered = len(TRACER._spans)
        assert n_buffered > 0
        import greptimedb_tpu.servers.http as http_mod

        real = http_mod._ingest_columns

        def boom(*a, **k):
            raise RuntimeError("ingest down")

        mon = SelfMonitor(db)
        monkeypatch.setattr(http_mod, "_ingest_columns", boom)
        with pytest.raises(RuntimeError):
            mon.flush_spans()
        assert len(TRACER._spans) == n_buffered  # requeued, not lost
        assert mon.spans_exported == 0
        monkeypatch.setattr(http_mod, "_ingest_columns", real)
        assert mon.flush_spans() == n_buffered

    def test_self_monitor_information_schema(self, db):
        r = db.sql("SELECT enabled, ticks FROM "
                   "information_schema.self_monitor")
        assert r.rows == [["No", 0]]

    def test_env_knob_starts_and_stops_timer(self, monkeypatch):
        monkeypatch.setenv("GREPTIME_SELF_MONITOR", "on")
        monkeypatch.setenv("GREPTIME_SELF_MONITOR_INTERVAL_S", "3600")
        d = GreptimeDB()
        try:
            assert d.self_monitor is not None
            assert d.self_monitor._thread.is_alive()
            r = d.sql("SELECT enabled FROM information_schema.self_monitor")
            assert r.rows == [["Yes"]]
        finally:
            d.close()
        assert d.self_monitor._thread is None  # stop() joined the timer


# ---------------------------------------------------------------------------
# Recursion guard: export ticks observe nothing about themselves
# ---------------------------------------------------------------------------

class TestRecursionGuard:
    def test_idle_ticks_emit_no_spans_or_slow_queries(self, db, traced):
        db.slow_query_threshold_ms = 0.0001
        try:
            mon = SelfMonitor(db)
            outs = [mon.tick() for _ in range(4)]
        finally:
            db.slow_query_threshold_ms = 0.0
        # export writes never span themselves: the buffer stays empty and
        # every tick after the first flushes zero spans
        assert all(o["spans"] == 0 for o in outs)
        assert TRACER._spans == []
        # and never trip the slow-query recorder (the table was never
        # even created on this idle instance)
        assert not db.catalog.table_exists("greptime_private", "slow_queries")

    def test_suppressed_blocks_span_recording(self, traced):
        with TRACER.suppressed():
            with TRACER.stage("should_not_record"):
                pass
            with TRACER.span("also_not_recorded"):
                pass
        with TRACER.stage("recorded"):
            pass
        assert [s["name"] for s in TRACER.drain()] == ["recorded"]

    def test_export_does_not_observe_protocol_latency(self, db, traced):
        from greptimedb_tpu.utils.telemetry import REGISTRY

        mon = SelfMonitor(db)
        mon.tick()
        before = {
            p: REGISTRY.value("greptime_protocol_query_duration_seconds",
                              (p,))
            for p in ("http", "mysql", "postgres", "prometheus")
        }
        mon.tick()
        after = {
            p: REGISTRY.value("greptime_protocol_query_duration_seconds",
                              (p,))
            for p in before
        }
        assert after == before


# ---------------------------------------------------------------------------
# Zero-overhead when disabled
# ---------------------------------------------------------------------------

class TestDisabledZeroOverhead:
    def test_disabled_instance_never_imports_exporter(self, monkeypatch):
        monkeypatch.delenv("GREPTIME_SELF_MONITOR", raising=False)
        mod = sys.modules.pop("greptimedb_tpu.utils.selfmonitor", None)
        try:
            d = GreptimeDB()
            d.sql("CREATE TABLE t0 (ts TIMESTAMP(3) TIME INDEX, v DOUBLE)")
            d.sql("INSERT INTO t0 VALUES (1000, 1.0)")
            d.sql("SELECT avg(v) FROM t0")
            assert d.self_monitor is None
            assert "greptimedb_tpu.utils.selfmonitor" not in sys.modules
            d.close()
        finally:
            if mod is not None:
                sys.modules["greptimedb_tpu.utils.selfmonitor"] = mod

    def test_disabled_tracer_stage_records_no_span(self):
        # the stage boundary is always a timer (histogram + profiler
        # annotation); only the span record is the tracer's
        from greptimedb_tpu.utils.telemetry import REGISTRY

        assert not TRACER.enabled
        n0 = REGISTRY.value("greptime_query_stage_seconds", ("anything",))
        with TRACER.stage("anything", rows=1) as st:
            pass
        assert TRACER._spans == [] and st.seconds >= 0.0
        assert REGISTRY.value(
            "greptime_query_stage_seconds", ("anything",)) == n0 + 1


# ---------------------------------------------------------------------------
# The full loop over HTTP: traceparent in → header out → flush → Jaeger
# ---------------------------------------------------------------------------

class TestHttpLoop:
    def test_full_loop(self):
        from greptimedb_tpu.servers import HttpServer

        d = GreptimeDB()
        d.sql("CREATE TABLE cpu (h STRING, ts TIMESTAMP(3) TIME INDEX, "
              "v DOUBLE, PRIMARY KEY (h))")
        d.sql("INSERT INTO cpu VALUES ('a', 1000, 1.0), ('b', 2000, 2.0)")
        srv = HttpServer(d, port=0)
        srv.start()
        TRACER.configure(enabled=True)
        TRACER.drain()
        base = f"http://127.0.0.1:{srv.port}"
        try:
            body = urllib.parse.urlencode(
                {"sql": "SELECT h, avg(v) FROM cpu GROUP BY h"}).encode()
            req = urllib.request.Request(
                f"{base}/v1/sql", data=body, method="POST",
                headers={"Content-Type": "application/x-www-form-urlencoded",
                         "traceparent": TP})
            with urllib.request.urlopen(req) as resp:
                assert resp.status == 200
                assert resp.headers["x-greptime-trace-id"] == TID
            # close the loop: loopback-export, then read the SAME trace
            # back through this instance's own Jaeger API
            mon = SelfMonitor(d)
            assert mon.flush_spans() > 0
            with urllib.request.urlopen(
                    f"{base}/v1/jaeger/api/traces/{TID}") as resp:
                payload = json.loads(resp.read())
            ops = {s["operationName"]
                   for t in payload["data"] for s in t["spans"]}
            assert {"sql", "execute", "materialize"} <= ops
            # metrics half: self-import, then PromQL over a registry
            # counter through the same instance
            mon.export_metrics()
            now = int(time.time())
            q = urllib.parse.urlencode({"sql": (
                f"TQL EVAL ({now - 60}, {now + 60}, '30s') "
                "greptime_protocol_query_duration_seconds_count")})
            with urllib.request.urlopen(f"{base}/v1/sql?{q}") as resp:
                payload = json.loads(resp.read())
            assert payload["output"][0]["records"]["rows"]
            # malformed traceparent: ignored, fresh trace id returned
            req = urllib.request.Request(
                f"{base}/v1/sql", data=body, method="POST",
                headers={"Content-Type": "application/x-www-form-urlencoded",
                         "traceparent": "00-banana-split-01"})
            with urllib.request.urlopen(req) as resp:
                assert resp.status == 200
                fresh = resp.headers["x-greptime-trace-id"]
                assert fresh and fresh != TID
        finally:
            TRACER.disable()
            srv.stop()
            d.close()
