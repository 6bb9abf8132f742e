"""HTTP server: SQL API, Prometheus API emulation, ingest protocols, admin.

Route surface mirrors the reference's make_app (src/servers/src/http.rs:775):

    /v1/sql                         SQL (greptime JSON envelope)
    /v1/promql                      native PromQL range query
    /v1/prometheus/api/v1/query          instant query
    /v1/prometheus/api/v1/query_range    range query
    /v1/prometheus/api/v1/labels         label names
    /v1/prometheus/api/v1/label/{n}/values
    /v1/prometheus/api/v1/series         series metadata
    /v1/prometheus/write            remote write (snappy protobuf)
    /v1/influxdb/api/v2/write       line protocol (also /v1/influxdb/write)
    /health /metrics /config /status

Runs the (synchronous) database in a thread-pool executor so the event
loop stays responsive; a dedicated thread hosts the loop so tests and the
standalone binary can start/stop it synchronously.
"""

from __future__ import annotations

import asyncio
import json
import re
import threading
import time

import numpy as np
from aiohttp import web

from greptimedb_tpu import native
from greptimedb_tpu.errors import (
    GreptimeError, InvalidArguments, StatusCode, TableNotFound,
)
from greptimedb_tpu.promql import format as prom_format
from greptimedb_tpu.query.engine import ColumnRows, QueryResult
from greptimedb_tpu.utils import telemetry
from greptimedb_tpu.utils.snappy import decompress as snappy_decompress
from greptimedb_tpu.utils.tracing import (
    GC_PAUSE, TRACER, parse_trace_id, parse_traceparent,
)

M_REQUESTS = telemetry.REGISTRY.counter(
    "greptime_http_requests_total", "HTTP requests", ("path", "code")
)
M_LATENCY = telemetry.REGISTRY.histogram(
    "greptime_http_request_duration_seconds", "HTTP latency", ("path",)
)
# how a reply's body was built: "columns" is the native encoder over
# whole columns or arrays, "rows" json.dumps over a list of rows or points
# (_json_reply, _prom_reply)
M_REPLY_ENCODED = telemetry.REGISTRY.counter(
    "greptime_http_reply_encoded_total", "Reply bodies by encoder",
    ("route", "encoder")
)
M_INGEST_ROWS = telemetry.REGISTRY.counter(
    "greptime_ingest_rows_total", "Rows ingested", ("protocol",)
)
M_INGEST_BYTES = telemetry.REGISTRY.counter(
    "greptime_ingest_bytes_total", "Wire bytes ingested (pre-decode)",
    ("protocol",)
)
# Per-protocol query latency (reference METRIC_HTTP_SQL_ELAPSED et al):
# one histogram shared by every wire surface — http SQL, the Prometheus
# API emulation, MySQL and PostgreSQL register their own labels on it.
M_PROTOCOL_QUERY = telemetry.REGISTRY.histogram(
    "greptime_protocol_query_duration_seconds",
    "Query latency by wire protocol", ("protocol",)
)


def _request_trace_context(request) -> tuple[str, str] | None:
    """Trace context for one query request: W3C ``traceparent`` first,
    then the reference's ``x-greptime-trace-id`` header; malformed values
    are ignored (fresh trace), never errors.  With the tracer on and no
    client context, a fresh trace id is minted so the response header
    always names the trace the query's spans landed in."""
    ctx = parse_traceparent(request.headers.get("traceparent"))
    if ctx is None:
        ctx = parse_trace_id(request.headers.get("x-greptime-trace-id"))
    if ctx is None and TRACER.enabled:
        ctx = (TRACER.new_trace_id(), "")
    return ctx


def _trace_headers(ctx: tuple[str, str] | None) -> dict:
    return {"x-greptime-trace-id": ctx[0]} if ctx else {}


def _result_to_json(res: QueryResult, t0: float) -> dict:
    if res.column_names:
        types = res.column_types or ["String"] * len(res.column_names)
        records = {
            "schema": {
                "column_schemas": [
                    {"name": n, "data_type": t}
                    for n, t in zip(res.column_names, types)
                ]
            },
            # whole columns where the engine left them so (_json_reply)
            "rows": res.rows if res.columns is None else res.columns,
            "total_rows": res.num_rows,
        }
        output = [{"records": records}]
    else:
        output = [{"affectedrows": res.affected_rows}]
    return {
        "code": 0,
        "output": output,
        "execution_time_ms": int((time.perf_counter() - t0) * 1000),
    }


def _json_reply(body: dict, route: str, headers: dict | None = None
                ) -> web.Response:
    """The response for what ``_result_to_json`` returned.  Where its
    ``rows`` are still whole columns (ColumnRows) of kinds the native
    encoder knows, that writes them in one call and the envelope is
    ``json.dumps`` around its bytes; anything else (no records, a list of
    rows, an object column of something other than text, no library) is
    ``json.dumps`` over the rows.  Both give the same bytes."""
    records = body["output"][0].get("records")
    rows = records["rows"] if records else None
    if isinstance(rows, ColumnRows):
        encoded = native.json_rows(rows.columns)
        if encoded is not None:
            encoded = native.json_around(body, records, "rows", encoded)
        if encoded is not None:
            M_REPLY_ENCODED.labels(route, "columns").inc()
            return web.Response(
                body=encoded, content_type="application/json",
                charset="utf-8", headers=headers)
        records["rows"] = rows.to_rows()
    M_REPLY_ENCODED.labels(route, "rows").inc()
    return web.json_response(body, headers=headers)


def _prom_reply(payload: dict, route: str, headers: dict | None = None
                ) -> web.Response:
    """The response for a PromQL payload, as ``_json_reply`` is for a SQL
    result: a matrix whose ``result`` is still the untouched array holder
    is written by the native encoder (promql/format.py ``payload_body``),
    anything else by ``json.dumps``, and the counter says which."""
    body, encoder = prom_format.payload_body(payload)
    M_REPLY_ENCODED.labels(route, encoder).inc()
    return web.Response(body=body, content_type="application/json",
                        charset="utf-8", headers=headers)


def _error_json(e: Exception) -> tuple[dict, int]:
    if isinstance(e, GreptimeError):
        code = e.status_code
        http = {
            StatusCode.TABLE_NOT_FOUND: 404,
            StatusCode.DATABASE_NOT_FOUND: 404,
            StatusCode.FLOW_NOT_FOUND: 404,
            StatusCode.INVALID_SYNTAX: 400,
            StatusCode.INVALID_ARGUMENTS: 400,
            StatusCode.PLAN_QUERY: 400,
            StatusCode.UNSUPPORTED: 400,
            StatusCode.TABLE_ALREADY_EXISTS: 409,
            StatusCode.DATABASE_ALREADY_EXISTS: 409,
            # deliberate backpressure (memory quota), not a server fault
            StatusCode.RUNTIME_RESOURCES_EXHAUSTED: 503,
            # per-tenant flow control (serving/admission.py): the client
            # should back off, not fail over
            StatusCode.RATE_LIMITED: 429,
            # scheduler deadline shed under overload
            StatusCode.DEADLINE_EXCEEDED: 503,
        }.get(code, 500)
        return {"code": int(code), "error": e.msg, "execution_time_ms": 0}, http
    return {"code": int(StatusCode.INTERNAL), "error": str(e)}, 500


class ThreadedAiohttpApp:
    """The ONE loop-hosting recipe for aiohttp servers on a daemon
    thread: build_app() on the loop thread, bind (port 0 = pick free),
    fail loudly if boot does not complete or errors, stop via the
    loop's own teardown. HttpServer and the frontend-role server both
    use this — boot/shutdown fixes land in one place."""

    thread_name = "greptime-http"

    def build_app(self):  # pragma: no cover — subclass contract
        raise NotImplementedError

    def start(self) -> None:
        if getattr(self, "_started", None) is None:
            self._started = threading.Event()
        GC_PAUSE.install()

        def run_loop():
            loop = asyncio.new_event_loop()
            asyncio.set_event_loop(loop)
            self._loop = loop
            try:
                app = self.build_app()
                runner = web.AppRunner(app)
                loop.run_until_complete(runner.setup())
                site = web.TCPSite(
                    runner, self.host, self.port,
                    ssl_context=getattr(self, "ssl_context", None))
                loop.run_until_complete(site.start())
                self._runner = runner
                if self.port == 0:
                    self.port = runner.addresses[0][1]
            except BaseException as e:  # noqa: BLE001 — surfaced by start()
                self._start_error = e
                self._started.set()
                return
            self._started.set()
            loop.run_forever()
            loop.run_until_complete(runner.cleanup())
            loop.close()

        self._start_error = None
        self._thread = threading.Thread(target=run_loop, daemon=True,
                                        name=self.thread_name)
        self._thread.start()
        if not self._started.wait(timeout=10):
            raise RuntimeError("http server failed to start (boot timeout)")
        if self._start_error is not None:
            raise self._start_error

    def stop(self) -> None:
        if getattr(self, "_loop", None) is not None:
            self._loop.call_soon_threadsafe(self._loop.stop)
        if getattr(self, "_thread", None) is not None:
            self._thread.join(timeout=5)


class HttpServer(ThreadedAiohttpApp):
    def __init__(self, db, host: str = "127.0.0.1", port: int = 4000, *,
                 ssl_context=None):
        self.db = db
        self.host = host
        self.port = port
        self.ssl_context = ssl_context
        self._thread: threading.Thread | None = None
        self._loop: asyncio.AbstractEventLoop | None = None
        self._started = threading.Event()
        self._runner = None
        # the database is single-writer (region sequence assignment and
        # memtable mutation are unsynchronized, like mito2's per-region
        # worker loop) — serialize all DB work on one executor thread.
        # Registry-only statements (KILL, SHOW PROCESSLIST) bypass the
        # pool via db.try_fast_sql so they cannot queue behind the very
        # query they target.
        from concurrent.futures import ThreadPoolExecutor

        self._db_executor = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="greptime-db"
        )
        # query requests block in scheduler.submit instead of executing
        # here — a wider pool lets concurrent clients queue into the
        # scheduler (where priorities, quotas and batching decide order)
        # rather than serialize in front of it
        self._submit_pool = ThreadPoolExecutor(
            max_workers=32, thread_name_prefix="greptime-submit")
        # metric-ingest handlers get their own small pool: region writes
        # serialize per REGION (Region._write_lock), so concurrent
        # batches for different tables/regions decode+append in parallel
        # instead of queueing behind one db-executor thread.  Width 1
        # (GREPTIME_INGEST_WORKERS=1) restores the strictly serialized
        # seed behavior.
        import os as _os

        self._ingest_pool = ThreadPoolExecutor(
            max_workers=max(1, int(_os.environ.get(
                "GREPTIME_INGEST_WORKERS", "4"))),
            thread_name_prefix="greptime-ingest")

    # ------------------------------------------------------------------
    def build_app(self) -> web.Application:
        @web.middleware
        async def auth_middleware(request: web.Request, handler):
            provider = getattr(self.db, "user_provider", None)
            if (
                provider is not None
                and provider.enabled
                and request.path not in ("/health", "/ready", "/metrics")
            ):
                if not provider.check_http_basic(
                    request.headers.get("Authorization")
                ):
                    return web.json_response(
                        {"code": int(StatusCode.USER_PASSWORD_MISMATCH),
                         "error": "authentication failed"},
                        status=401,
                        headers={"WWW-Authenticate": 'Basic realm="greptime"'},
                    )
            return await handler(request)

        app = web.Application(client_max_size=64 * 1024 * 1024,
                              middlewares=[auth_middleware])
        r = app.router
        r.add_route("*", "/v1/sql", self.h_sql)
        r.add_route("*", "/v1/promql", self.h_promql)
        r.add_route("*", "/v1/prometheus/api/v1/query", self.h_prom_query)
        r.add_route("*", "/v1/prometheus/api/v1/query_range", self.h_prom_range)
        r.add_route("*", "/v1/prometheus/api/v1/labels", self.h_prom_labels)
        r.add_get("/v1/prometheus/api/v1/label/{name}/values", self.h_prom_label_values)
        r.add_route("*", "/v1/prometheus/api/v1/series", self.h_prom_series)
        r.add_post("/v1/prometheus/write", self.h_remote_write)
        r.add_post("/v1/prometheus/read", self.h_remote_read)
        r.add_post("/v1/influxdb/api/v2/write", self.h_influx_write)
        r.add_post("/v1/influxdb/write", self.h_influx_write)
        r.add_post("/v1/arrow/write", self.h_arrow_write)
        r.add_post("/v1/otlp/v1/metrics", self.h_otlp_metrics)
        r.add_post("/v1/otlp/v1/logs", self.h_otlp_logs)
        r.add_post("/v1/otel-arrow/v1/metrics", self.h_otel_arrow_metrics)
        r.add_post("/v1/loki/api/v1/push", self.h_loki_push)
        r.add_route("*", "/v1/loki/api/v1/query", self.h_loki_query)
        r.add_route("*", "/v1/loki/api/v1/query_range",
                    self.h_loki_query_range)
        r.add_route("*", "/v1/loki/api/v1/labels", self.h_loki_labels)
        r.add_get("/v1/loki/api/v1/label/{name}/values",
                  self.h_loki_label_values)
        r.add_route("*", "/v1/loki/api/v1/series", self.h_loki_series)
        r.add_post("/v1/logs", self.h_log_query)
        r.add_post("/v1/otlp/v1/traces", self.h_otlp_traces)
        r.add_get("/v1/jaeger/api/services", self.h_jaeger_services)
        r.add_get("/v1/jaeger/api/operations", self.h_jaeger_operations)
        r.add_get("/v1/jaeger/api/services/{service}/operations",
                  self.h_jaeger_service_operations)
        r.add_get("/v1/jaeger/api/traces/{trace_id}", self.h_jaeger_trace)
        r.add_get("/v1/jaeger/api/traces", self.h_jaeger_find)
        r.add_post("/v1/opentsdb/api/put", self.h_opentsdb_put)
        r.add_post("/v1/elasticsearch/_bulk", self.h_es_bulk)
        r.add_post("/v1/elasticsearch/{index}/_bulk", self.h_es_bulk)
        r.add_get("/v1/elasticsearch/", self.h_es_info)
        r.add_get("/v1/elasticsearch/_license", self.h_es_license)
        r.add_post("/v1/splunk/services/collector", self.h_splunk_hec)
        r.add_post("/v1/splunk/services/collector/event", self.h_splunk_hec)
        r.add_post("/v1/pipelines/{name}", self.h_pipeline_upsert)
        r.add_delete("/v1/pipelines/{name}", self.h_pipeline_delete)
        r.add_get("/v1/pipelines", self.h_pipeline_list)
        r.add_post("/v1/ingest", self.h_ingest)
        r.add_get("/health", self.h_health)
        r.add_route("*", "/debug/log_level", self.h_log_level)
        r.add_get("/debug/prof/cpu", self.h_prof_cpu)
        r.add_route("*", "/debug/prof/mem", self.h_prof_mem)
        r.add_get("/ready", self.h_health)
        r.add_get("/metrics", self.h_metrics)
        r.add_get("/config", self.h_config)
        r.add_get("/status", self.h_status)
        r.add_get("/v1/slo", self.h_slo)
        r.add_get("/dashboard", self.h_dashboard)
        r.add_get("/dashboard/", self.h_dashboard)
        return app

    async def _call(self, fn, *args):
        return await asyncio.get_running_loop().run_in_executor(
            self._db_executor, fn, *args
        )

    async def _call_ingest(self, fn, *args):
        return await asyncio.get_running_loop().run_in_executor(
            self._ingest_pool, fn, *args
        )

    def _admit_ingest(self, request: web.Request, wire_bytes: int,
                      tenant: str | None = None):
        """Per-tenant write admission (PR 7 discipline, applied to the
        write path): reserve the batch's estimated decoded footprint
        against the tenant's memory budget and count it in flight, so
        sustained ingest cannot starve interactive queries of their
        memory/concurrency quotas.  Returns a release callable (pair it
        in a finally); raises RateLimited (429) / ResourcesExhausted
        (503) — the same error surface queries get."""
        adm = self.db.scheduler.admission
        if tenant is None:
            tenant = self._tenant(request)
        # decoded columnar batches run ~4x the wire bytes (numbers widen
        # to float64/int64, tag codes add int32 per row)
        est = wire_bytes * 4
        adm.admit(tenant, est)
        return lambda: adm.release(tenant, est)

    async def _call_query(self, fn, *args):
        """Query-path executor hop: the scheduler-submit pool (submit
        blocks until the worker finishes the entry)."""
        return await asyncio.get_running_loop().run_in_executor(
            self._submit_pool, fn, *args)

    def _tenant(self, request: web.Request) -> str:
        """Tenant identity for admission: the authenticated basic-auth
        username wins (a client must not be able to shed its quotas by
        sending a different x-greptime-tenant header); the header is the
        fallback for unauthenticated deployments, else "default"."""
        auth = request.headers.get("Authorization", "")
        if auth.startswith("Basic "):
            import base64

            try:
                creds = base64.b64decode(auth[6:]).decode("utf-8")
                user = creds.split(":", 1)[0]
                if user:
                    return user
            except Exception:  # noqa: BLE001 — auth middleware rejects
                pass
        return request.headers.get("x-greptime-tenant") or "default"

    def _loki_tenant(self, request: web.Request) -> str:
        """Loki surfaces speak multi-tenancy via ``X-Scope-OrgID``
        (Loki's org header): it maps onto the SAME per-tenant admission
        budgets as every other surface.  Authenticated identity still
        wins — a client must not shed its quotas by sending a different
        org id."""
        auth = request.headers.get("Authorization", "")
        if auth.startswith("Basic "):
            return self._tenant(request)
        org = request.headers.get("X-Scope-OrgID")
        if org:
            return str(org)
        return self._tenant(request)

    @staticmethod
    def _priority(request: web.Request) -> str | None:
        p = request.headers.get("x-greptime-priority")
        return p if p in ("interactive", "normal", "background") else None

    async def _param(self, request: web.Request, name: str, default=None):
        if name in request.query:
            return request.query[name]
        if request.method == "POST" and request.content_type in (
            "application/x-www-form-urlencoded", "multipart/form-data",
        ):
            form = await request.post()
            if name in form:
                return form[name]
        return default

    # ---- handlers ------------------------------------------------------
    async def h_sql(self, request: web.Request) -> web.Response:
        ctx = _request_trace_context(request)
        with TRACER.stage_in(ctx, "http_request", path="/v1/sql"):
            return await self._h_sql(request, ctx)

    async def _h_sql(self, request: web.Request, ctx) -> web.Response:
        t0 = time.perf_counter()
        sql = await self._param(request, "sql")
        hold: list = []  # caller-held SLO sample (see scheduler._finish)
        with M_LATENCY.labels("/v1/sql").time():
            if not sql:
                M_REQUESTS.labels("/v1/sql", "400").inc()
                return web.json_response(
                    {"code": int(StatusCode.INVALID_ARGUMENTS),
                     "error": "missing sql parameter"}, status=400)
            try:
                # KILL / SHOW PROCESSLIST run inline (sub-ms, registry
                # lock only) so they never queue behind the statement
                # they target on the single-worker db executor
                res = self.db.try_fast_sql(sql)
                timed = res is None
                if res is None:
                    tenant = self._tenant(request)
                    prio = self._priority(request)
                    client = request.remote or ""
                    res = await self._call_query(
                        lambda: self.db.scheduler.submit(
                            sql, tenant=tenant, priority=prio,
                            client=client, trace_ctx=ctx,
                            protocol="http", slo_hold=hold))
                # serialize BEFORE observing (ISSUE 18 fix): the JSON
                # envelope and its text are part of what the client
                # waits for, and the histogram previously closed at
                # submit-return — under-reporting exactly the rows-heavy
                # responses.  The scheduler's SLO sample is caller-held
                # over the same span (record_held below), so sketch and
                # histogram agree by construction.
                with TRACER.stage_in(ctx, "serialize"):
                    resp = _json_reply(_result_to_json(res, t0), "/v1/sql",
                                       headers=_trace_headers(ctx))
                if timed:
                    M_PROTOCOL_QUERY.labels("http").observe(
                        time.perf_counter() - t0)
                    self.db.scheduler.record_held(hold)
                M_REQUESTS.labels("/v1/sql", "200").inc()
                return resp
            except Exception as e:  # noqa: BLE001
                # serialization failed after a clean execution: the
                # held sample still records (exactly-one invariant)
                self.db.scheduler.record_held(hold)
                body, status = _error_json(e)
                M_REQUESTS.labels("/v1/sql", str(status)).inc()
                return web.json_response(body, status=status,
                                         headers=_trace_headers(ctx))

    async def _eval_promql(self, query: str, start: float, end: float,
                           step: float, lookback: float | None = None,
                           trace_ctx: tuple[str, str] | None = None,
                           tenant: str = "default"):
        """(result with its values on the host, step timestamps)."""
        import dataclasses

        from greptimedb_tpu.promql.engine import DEFAULT_LOOKBACK_S, PromEvaluator
        from greptimedb_tpu.promql.parser import parse_promql

        with TRACER.stage_in(trace_ctx, "parse"):
            expr = parse_promql(query)

        def run():
            with M_PROTOCOL_QUERY.labels("prometheus").time():
                with TRACER.trace_context(trace_ctx):
                    ev = PromEvaluator(self.db, start, end, step,
                                       lookback or DEFAULT_LOOKBACK_S)
                    res = ev.eval(expr)
                    # the one place the result leaves the device: here,
                    # on the worker, so the event loop formats host data
                    # and never waits for the chip
                    with TRACER.stage("device_wait"):
                        res = dataclasses.replace(
                            res, values=np.asarray(res.values))
            return res, ev.steps_ms()

        # PromQL evaluations submit like SQL queries: per-tenant
        # admission, interactive priority, deadline shedding (no
        # cross-query batching — the PromQL layout caches already
        # dedupe the heavy state)
        return await self._call_query(
            lambda: self.db.scheduler.submit_fn(
                run, tenant=tenant, trace_ctx=trace_ctx,
                label=query[:256], protocol="prometheus"))

    async def _h_prom(self, request: web.Request, route: str, params,
                      payload_name: str) -> web.Response:
        """query_range and query: ``params(request)`` gives (query,
        start, end, step); the histogram covers the handler to the
        built response, as /v1/sql's does (ISSUE 18)."""
        ctx = _request_trace_context(request)
        with TRACER.stage_in(ctx, "http_request", path=route), \
                M_LATENCY.labels(route).time():
            try:
                query, start, end, step = await params(request)
                res, steps = await self._eval_promql(
                    query, start, end, step, trace_ctx=ctx,
                    tenant=self._tenant(request))
                with TRACER.stage_in(ctx, "format"):
                    payload = getattr(prom_format, payload_name)(res, steps)
                with TRACER.stage_in(ctx, "serialize"):
                    resp = _prom_reply(payload, route, _trace_headers(ctx))
                M_REQUESTS.labels(route, "200").inc()
                return resp
            except Exception as e:  # noqa: BLE001
                M_REQUESTS.labels(route, "400").inc()
                return web.json_response(
                    {"status": "error", "errorType": "bad_data",
                     "error": str(e)}, status=400)

    async def h_prom_range(self, request: web.Request) -> web.Response:
        async def params(request):
            return (
                await self._param(request, "query"),
                _parse_prom_time(await self._param(request, "start")),
                _parse_prom_time(await self._param(request, "end")),
                _parse_prom_duration(
                    await self._param(request, "step", "60")))

        return await self._h_prom(
            request, "/v1/prometheus/api/v1/query_range", params,
            "range_payload")

    async def h_prom_query(self, request: web.Request) -> web.Response:
        async def params(request):
            t = _parse_prom_time(
                await self._param(request, "time", str(time.time())))
            return await self._param(request, "query"), t, t, 1

        return await self._h_prom(
            request, "/v1/prometheus/api/v1/query", params,
            "instant_payload")

    async def h_prom_labels(self, request: web.Request) -> web.Response:
        def run():
            names = {"__name__"}
            for t in self.db.catalog.list_tables(self.db.current_db):
                for c in t.schema.tag_columns:
                    names.add(c.name)
            return sorted(names)

        data = await self._call(run)
        return web.json_response({"status": "success", "data": data})

    async def h_prom_label_values(self, request: web.Request) -> web.Response:
        name = request.match_info["name"]

        def run():
            if name == "__name__":
                return sorted(
                    t.name for t in self.db.catalog.list_tables(self.db.current_db)
                )
            values = set()
            for t in self.db.catalog.list_tables(self.db.current_db):
                if any(c.name == name for c in t.schema.tag_columns):
                    # _table_view merges all partitions' dictionaries
                    view = self.db._table_view(t.name)
                    enc = view.encoders.get(name)
                    if enc:
                        values.update(str(v) for v in enc.values())
            return sorted(values)

        data = await self._call(run)
        return web.json_response({"status": "success", "data": data})

    async def h_prom_series(self, request: web.Request) -> web.Response:
        matches = request.query.getall("match[]", [])
        if not matches and request.method == "POST":
            form = await request.post()
            matches = form.getall("match[]", [])

        def run():
            from greptimedb_tpu.promql.engine import SelectorData
            from greptimedb_tpu.promql.parser import parse_promql, VectorSelector

            out = []
            for m in matches:
                e = parse_promql(m)
                if not isinstance(e, VectorSelector):
                    continue
                try:
                    d = SelectorData(self.db, e.metric)
                except GreptimeError:
                    continue
                _tsids, _sel_dev, labels = d.select_series(e.matchers)
                for lab in labels:
                    item = {"__name__": e.metric}
                    item.update({k: str(v) for k, v in lab.items()})
                    out.append(item)
            return out

        data = await self._call(run)
        return web.json_response({"status": "success", "data": data})

    async def h_remote_write(self, request: web.Request) -> web.Response:
        from greptimedb_tpu.servers.protocols import parse_remote_write

        body = await request.read()
        if request.headers.get("Content-Encoding", "snappy").lower() == "snappy":
            try:
                body = snappy_decompress(body)
            except ValueError as e:
                return web.json_response({"error": f"snappy: {e}"}, status=400)

        def run():
            from greptimedb_tpu.errors import InvalidArguments

            tables = parse_remote_write(body)
            total = 0
            for table, cols in tables.items():
                # Prometheus metrics multiplex onto the metric engine's
                # physical region (reference default for remote write);
                # names already taken by plain tables fall back to them so
                # one conflicting metric can't wedge the whole batch.
                # The DDL lock serializes ONLY logical-table/label-set
                # growth across the ingest pool — the append itself runs
                # outside it (the shared physical region's own write lock
                # serializes appends), so one batch's WAL flush never
                # stalls unrelated tables' ingest on the DDL lock.
                name = _safe_table(table)
                try:
                    with _INGEST_DDL_LOCK:
                        self.db.metric_engine.ensure_logical(
                            name, list(cols.get("__tags__") or []))
                    total += self.db.metric_engine.write(name, cols,
                                                         ensure=False)
                except InvalidArguments:
                    total += _ingest_columns(self.db, name, cols)
            cache = getattr(self.db, "cache", None)
            if tables and cache is not None:
                # hot-tail: freshly acked samples scatter into the
                # physical region's resident grid tail (if any)
                cache.extend_hot_tail(self.db.metric_engine.physical_region())
            if self.db.flow_engine.flows:
                with _INGEST_DDL_LOCK:
                    for table, cols in tables.items():
                        # metric-engine writes multiplex regions;
                        # conservative appendable=False is handled upstream
                        # via dirtying, so pass the chunk and let pure
                        # appends stream
                        self.db.flow_engine.on_write(_safe_table(table),
                                                     cols["ts"], data=cols)
                    self.db.flow_engine.run_all()
            return total

        M_INGEST_BYTES.labels("prom_remote_write").inc(len(body))
        try:
            release = self._admit_ingest(request, len(body))
            try:
                n = await self._call_ingest(run)
            finally:
                release()
            M_INGEST_ROWS.labels("prom_remote_write").inc(n)
            return web.Response(status=204)
        except Exception as e:  # noqa: BLE001
            body_json, status = _error_json(e)
            return web.json_response(body_json, status=status)

    async def h_influx_write(self, request: web.Request) -> web.Response:
        from greptimedb_tpu.servers.protocols import parse_line_protocol

        # raw bytes: the vectorized parser consumes them directly (one
        # C-level transform + pyarrow CSV); the legacy path decodes
        body = await request.read()
        precision = request.query.get("precision", "ns")
        M_INGEST_BYTES.labels("influxdb").inc(len(body))

        def run():
            tables = parse_line_protocol(body, precision)
            total = 0
            for table, cols in tables.items():
                total += _ingest_columns(self.db, table, cols)
            return total

        try:
            release = self._admit_ingest(request, len(body))
            try:
                n = await self._call_ingest(run)
            finally:
                release()
            M_INGEST_ROWS.labels("influxdb").inc(n)
            return web.Response(status=204)
        except Exception as e:  # noqa: BLE001
            body_json, status = _error_json(e)
            return web.json_response(body_json, status=status)

    async def h_arrow_write(self, request: web.Request) -> web.Response:
        """Arrow IPC bulk insert — the standalone HTTP surface of the
        in-cluster Flight do_put plane (reference gRPC bulk inserts).
        Body: one Arrow IPC stream; ``?table=`` names the target.  The
        highest-rate wire format: columns land as NumPy arrays /
        dictionary codes with zero per-row decode (protocols.py
        ``parse_arrow_bulk``)."""
        from greptimedb_tpu.servers.protocols import parse_arrow_bulk

        table = request.query.get("table", "")
        body = await request.read()
        M_INGEST_BYTES.labels("arrow").inc(len(body))

        def run():
            if not table:
                raise InvalidArguments("arrow write needs ?table=")
            cols = parse_arrow_bulk(body)
            return _ingest_columns(self.db, table, cols)

        try:
            release = self._admit_ingest(request, len(body))
            try:
                n = await self._call_ingest(run)
            finally:
                release()
            M_INGEST_ROWS.labels("arrow").inc(n)
            return web.json_response({"rows": n})
        except Exception as e:  # noqa: BLE001
            body_json, status = _error_json(e)
            return web.json_response(body_json, status=status)

    async def h_otlp_metrics(self, request: web.Request) -> web.Response:
        from greptimedb_tpu.servers.otlp import parse_otlp_metrics

        # aiohttp transparently inflates Content-Encoding: gzip on read()
        try:
            body = await request.read()
        except Exception as e:  # noqa: BLE001 (bad content encoding etc.)
            return web.json_response({"error": f"body: {e}"}, status=400)

        def run():
            tables = parse_otlp_metrics(body)
            total = 0
            for table, cols in tables.items():
                total += _ingest_columns(self.db, table, cols)
            return total

        M_INGEST_BYTES.labels("otlp_metrics").inc(len(body))
        try:
            release = self._admit_ingest(request, len(body))
            try:
                n = await self._call_ingest(run)
            finally:
                release()
            M_INGEST_ROWS.labels("otlp_metrics").inc(n)
            return web.json_response({"partialSuccess": {}})
        except Exception as e:  # noqa: BLE001
            body_json, status = _error_json(e)
            return web.json_response(body_json, status=status)


    async def h_remote_read(self, request: web.Request) -> web.Response:
        """Prometheus remote read (reference src/servers/src/http/
        prom_store.rs): snappy ReadRequest in, snappy ReadResponse of raw
        samples out — series resolved by the same inverted-index matcher
        machinery the PromQL engine uses."""
        from greptimedb_tpu.promql.engine import SelectorData
        from greptimedb_tpu.promql.parser import LabelMatcher
        from greptimedb_tpu.servers.protocols import (
            encode_read_response, parse_remote_read,
        )
        from greptimedb_tpu.storage.memtable import TSID
        from greptimedb_tpu.utils.snappy import compress as snappy_compress

        body = await request.read()
        if request.headers.get("Content-Encoding", "snappy").lower() == "snappy":
            try:
                body = snappy_decompress(body)
            except Exception as e:  # noqa: BLE001
                return web.json_response({"error": f"snappy: {e}"}, status=400)

        def run():
            queries = parse_remote_read(body)
            results = []
            for q in queries:
                metric = next(
                    (v for op, n, v in q["matchers"]
                     if n == "__name__" and op == "="), None)
                if metric is None:
                    raise InvalidArguments(
                        "remote read needs an equality __name__ matcher")
                matchers = [LabelMatcher(n, op, v)
                            for op, n, v in q["matchers"]
                            if n != "__name__"]
                try:
                    data = SelectorData(self.db, metric)
                except TableNotFound:
                    results.append([])  # unknown metric: empty, not 5xx
                    continue
                tsids, _sel_dev, labels = data.select_series(matchers)
                field = data.field_column(matchers)
                # equality matchers prune SSTs via the bloom sidecars
                tag_filters = {
                    m.name: {m.value} for m in matchers
                    if m.op == "=" and m.name != "__field__"
                } or None
                host = data.region.scan_host(
                    (q["start_ms"], q["end_ms"] + 1),
                    tag_filters=tag_filters)
                import numpy as _np

                row_tsid = _np.asarray(host[TSID])
                keep = _np.isin(row_tsid, tsids)
                row_tsid = row_tsid[keep]
                ts_col = _np.asarray(
                    host[data.schema.time_index.name])[keep]
                val_col = _np.asarray(host[field])[keep]
                # scan_host rows are (tsid, ts)-sorted already: one
                # unique() split instead of a per-row Python loop
                uniq, starts = _np.unique(row_tsid, return_index=True)
                bounds = _np.append(starts, len(row_tsid))
                by_tsid = {int(t): i for i, t in enumerate(tsids)}
                series = []
                for j, t in enumerate(uniq):
                    li = by_tsid.get(int(t))
                    if li is None:
                        continue
                    sl = slice(bounds[j], bounds[j + 1])
                    vals, tss = val_col[sl], ts_col[sl]
                    ok = vals == vals  # NaN = absent
                    if not ok.any():
                        continue
                    lab = dict(labels[li])
                    lab["__name__"] = metric
                    series.append((lab, list(zip(
                        vals[ok].tolist(), tss[ok].tolist()))))
                results.append(series)
            return snappy_compress(encode_read_response(results))

        try:
            payload = await self._call(run)
            return web.Response(
                body=payload,
                content_type="application/x-protobuf",
                headers={"Content-Encoding": "snappy"},
            )
        except Exception as e:  # noqa: BLE001
            body_json, status = _error_json(e)
            return web.json_response(body_json, status=status)

    async def h_otlp_logs(self, request: web.Request) -> web.Response:
        """OTLP/HTTP logs ingest (reference src/servers/src/otlp/logs.rs):
        protobuf ExportLogsServiceRequest → rows in the log table
        (x-greptime-log-table-name, default opentelemetry_logs), optionally
        shaped by a named pipeline (x-greptime-pipeline-name; the default
        identity mapping mirrors greptime_identity)."""
        from greptimedb_tpu.servers.otlp import parse_otlp_logs

        table = request.headers.get("x-greptime-log-table-name",
                                    "opentelemetry_logs")
        pname = request.headers.get("x-greptime-pipeline-name")
        try:
            body = await request.read()
        except Exception as e:  # noqa: BLE001
            return web.json_response({"error": f"body: {e}"}, status=400)

        def run():
            rows = parse_otlp_logs(body)
            if not rows:
                return 0
            if pname and pname != "greptime_identity":
                pipe = self._pipelines().get(pname)
                cols = pipe.run(rows)
            else:
                names = list(rows[0].keys())
                cols = {k: [r.get(k) for r in rows] for k in names}
                cols["__tags__"] = []
                cols["__fields__"] = [n for n in names if n != "ts"]
            if not cols.get("ts"):
                return 0
            return _ingest_columns(self.db, table, cols, append_mode=True)

        try:
            n = await self._call(run)
            M_INGEST_ROWS.labels("otlp_logs").inc(n)
            return web.json_response({"partialSuccess": {}})
        except Exception as e:  # noqa: BLE001
            body_json, status = _error_json(e)
            return web.json_response(body_json, status=status)

    async def h_otel_arrow_metrics(self, request: web.Request) -> web.Response:
        """OTel-Arrow (OTAP) columnar metrics ingest (reference
        src/servers/src/otel_arrow.rs + otel-arrow-rust).  The body is
        an Arrow IPC stream of flattened univariate metric batches —
        columns: metric name (``name``/``metric_name``), a time column
        (``time_unix_nano``/``ts``/``timestamp``), a value column
        (``value``/``double_value``/``int_value``), every other column
        an attribute (tag).  Transport differs from the reference (HTTP
        body instead of a gRPC ArrowMetricsService stream — this server
        is HTTP-first; the in-cluster bulk path is Flight do_put), the
        data model is the same: one record batch, zero row-wise decode.
        """
        import pyarrow.ipc as pa_ipc

        try:
            body = await request.read()
        except Exception as e:  # noqa: BLE001
            return web.json_response({"error": f"body: {e}"}, status=400)

        def run():
            import io

            try:
                reader = pa_ipc.open_stream(io.BytesIO(body))
                tbl = reader.read_all()
            except Exception as e:
                raise InvalidArguments(f"bad arrow ipc stream: {e}")
            names = set(tbl.column_names)
            name_col = next(
                (c for c in ("name", "metric_name") if c in names), None)
            time_col = next(
                (c for c in ("time_unix_nano", "ts", "timestamp")
                 if c in names), None)
            val_col = next(
                (c for c in ("value", "double_value", "int_value")
                 if c in names), None)
            if not (name_col and time_col and val_col):
                raise InvalidArguments(
                    "otel-arrow batch needs name, time and value columns")
            metric_names = tbl.column(name_col).to_pylist()
            times = tbl.column(time_col).to_pylist()
            vals = tbl.column(val_col).to_pylist()
            if any(v is None for v in metric_names) or any(
                    t is None for t in times) or any(
                    v is None for v in vals):
                raise InvalidArguments(
                    "otel-arrow batch has null name/time/value cells")
            if time_col == "time_unix_nano":
                times = [t // 1_000_000 for t in times]
            attr_cols = {
                c: tbl.column(c).to_pylist() for c in tbl.column_names
                if c not in (name_col, time_col, val_col)
            }
            per_table: dict[str, list[int]] = {}
            for i, m in enumerate(metric_names):
                # prometheus-style name normalization (reference
                # translate_metric_name/normalize_metric_name): dots and
                # other specials → '_' so names never split as db.table
                safe = re.sub(r"[^a-zA-Z0-9_:]", "_", str(m))
                per_table.setdefault(safe, []).append(i)
            total = 0
            for table, idxs in per_table.items():
                tags = sorted(attr_cols)
                cols: dict[str, list] = {
                    k: [str(attr_cols[k][i]) if attr_cols[k][i] is not None
                        else "" for i in idxs]
                    for k in tags
                }
                cols["ts"] = [times[i] for i in idxs]
                cols["val"] = [vals[i] for i in idxs]
                cols["__tags__"] = tags
                cols["__fields__"] = ["val"]
                total += _ingest_columns(self.db, table, cols)
            return total

        try:
            n = await self._call(run)
            M_INGEST_ROWS.labels("otel_arrow").inc(n)
            return web.json_response({"status": {"status_code": 0},
                                      "rows": n})
        except Exception as e:  # noqa: BLE001
            body_json, status = _error_json(e)
            return web.json_response(body_json, status=status)

    async def h_loki_push(self, request: web.Request) -> web.Response:
        """Loki push (reference src/servers/src/http/loki.rs), BOTH wire
        forms: JSON and snappy-compressed protobuf (logproto.PushRequest
        — what promtail/the Grafana agent actually send).  Streams land
        in ``loki_logs`` with stream labels as tags, the line in ``line``
        (string field), and the admitted tenant (``X-Scope-OrgID``) as a
        ``tenant`` tag — queryable and joinable like any other label."""
        try:
            body = await request.read()
        except Exception as e:  # noqa: BLE001 (bad content encoding etc.)
            return web.json_response({"error": f"body: {e}"}, status=400)
        ctype = request.content_type or ""
        tenant = self._loki_tenant(request)

        def run():
            # decompress/decode on the executor thread, never the event
            # loop — promtail batches can be tens of MB
            rows: list[tuple[dict, str, int]] = []
            if "json" in ctype:
                try:
                    payload = json.loads(body)
                except json.JSONDecodeError as e:
                    raise InvalidArguments(f"bad json: {e}")
                for stream in payload.get("streams", []):
                    labels = (stream.get("stream") or {}).items()
                    labels = {str(k): str(v) for k, v in labels}
                    for entry in stream.get("values", []):
                        try:
                            ts_ns = int(entry[0])
                            line = str(entry[1])
                        except (ValueError, TypeError, IndexError) as e:
                            raise InvalidArguments(
                                f"bad loki entry {entry!r}: {e}")
                        rows.append((labels, line, ts_ns // 1_000_000))
            else:  # protobuf variant: snappy(logproto.PushRequest)
                from greptimedb_tpu.servers.protocols import parse_loki_push

                try:
                    raw = snappy_decompress(body)
                except Exception:  # noqa: BLE001 — some clients skip snappy
                    raw = body
                try:
                    rows = parse_loki_push(raw)
                except Exception as e:  # noqa: BLE001
                    raise InvalidArguments(f"bad protobuf push: {e}")

            # labels named like reserved columns are renamed
            rows = [
                ({(k + "_label" if k in ("ts", "line", "tenant") else k): v
                  for k, v in labels.items()}, line, ts)
                for labels, line, ts in rows
            ]
            if not rows:
                return 0
            tag_names = sorted({k for lab, _l, _t in rows for k in lab}
                               | {"tenant"})
            cols: dict[str, list] = {k: [] for k in tag_names}
            cols["ts"] = []
            cols["line"] = []
            for lab, line, ts in rows:
                for k in tag_names:
                    cols[k].append(tenant if k == "tenant"
                                   else lab.get(k, ""))
                cols["ts"].append(ts)
                cols["line"].append(line)
            cols["__tags__"] = tag_names
            cols["__fields__"] = ["line"]
            n = _ingest_columns(self.db, "loki_logs", cols,
                                append_mode=True)
            # ingest-side fingerprint hot tail: if the fulltext matrix is
            # already resident, extend it with this batch's new distinct
            # lines now (best-effort, non-blocking)
            from greptimedb_tpu.fulltext.loki import prewarm_ingest

            prewarm_ingest(self.db, "loki_logs")
            return n

        M_INGEST_BYTES.labels("loki").inc(len(body))
        try:
            release = self._admit_ingest(request, len(body), tenant=tenant)
            try:
                n = await self._call_ingest(run)
            finally:
                release()
            M_INGEST_ROWS.labels("loki").inc(n)
            return web.Response(status=204)
        except Exception as e:  # noqa: BLE001
            body_json, status = _error_json(e)
            return web.json_response(body_json, status=status)

    async def _loki_params(self, request: web.Request) -> dict:
        params = dict(request.query)
        if request.method == "POST" and request.content_type in (
            "application/x-www-form-urlencoded", "multipart/form-data",
        ):
            form = await request.post()
            for k in form:
                params.setdefault(k, form[k])
        return params

    async def _loki_eval(self, request: web.Request, path: str, fn):
        """Shared Loki read-endpoint plumbing: params, the query
        scheduler (tenant admission from ``X-Scope-OrgID``, interactive
        priority, deadline shedding), Loki-style error envelopes."""
        ctx = _request_trace_context(request)
        try:
            params = await self._loki_params(request)

            def run():
                with M_PROTOCOL_QUERY.labels("loki").time():
                    with TRACER.trace_context(ctx):
                        return fn(params)

            with M_LATENCY.labels(path).time():
                tenant = self._loki_tenant(request)
                payload = await self._call_query(
                    lambda: self.db.scheduler.submit_fn(
                        run, tenant=tenant,
                        label=f"logql: {params.get('query', path)}"
                        [:256], protocol="loki"))
            M_REQUESTS.labels(path, "200").inc()
            return web.json_response(payload, headers=_trace_headers(ctx))
        except Exception as e:  # noqa: BLE001
            _body, status = _error_json(e)
            M_REQUESTS.labels(path, str(status)).inc()
            return web.json_response(
                {"status": "error", "errorType": "bad_data", "error": str(e)},
                status=status, headers=_trace_headers(ctx))

    async def h_loki_query(self, request: web.Request) -> web.Response:
        from greptimedb_tpu.fulltext.loki import loki_query_instant

        return await self._loki_eval(
            request, "/v1/loki/api/v1/query",
            lambda params: loki_query_instant(self.db, params))

    async def h_loki_query_range(self, request: web.Request) -> web.Response:
        from greptimedb_tpu.fulltext.loki import loki_query_range

        return await self._loki_eval(
            request, "/v1/loki/api/v1/query_range",
            lambda params: loki_query_range(self.db, params))

    async def h_loki_labels(self, request: web.Request) -> web.Response:
        from greptimedb_tpu.fulltext.loki import loki_labels

        return await self._loki_eval(
            request, "/v1/loki/api/v1/labels",
            lambda params: loki_labels(self.db, params))

    async def h_loki_label_values(self, request: web.Request) -> web.Response:
        from greptimedb_tpu.fulltext.loki import loki_label_values

        name = request.match_info["name"]
        return await self._loki_eval(
            request, "/v1/loki/api/v1/label_values",
            lambda params: loki_label_values(self.db, name, params))

    async def h_loki_series(self, request: web.Request) -> web.Response:
        from greptimedb_tpu.fulltext.loki import loki_series

        matches = request.query.getall("match[]", [])
        if not matches and request.method == "POST":
            form = await request.post()
            matches = form.getall("match[]", [])
        return await self._loki_eval(
            request, "/v1/loki/api/v1/series",
            lambda params: loki_series(self.db, matches, params))

    async def h_log_query(self, request: web.Request) -> web.Response:
        from greptimedb_tpu.servers.logquery import execute_log_query

        t0 = time.perf_counter()
        try:
            query = json.loads(await request.read())
        except json.JSONDecodeError as e:
            return web.json_response({"error": f"bad json: {e}"}, status=400)
        try:
            res = await self._call(execute_log_query, self.db, query)
            return _json_reply(_result_to_json(res, t0), "/v1/logs")
        except (AttributeError, TypeError, KeyError) as e:
            # malformed-but-parseable request shapes are client errors
            return web.json_response({"error": f"bad log query: {e}"},
                                     status=400)
        except Exception as e:  # noqa: BLE001
            body_json, status = _error_json(e)
            return web.json_response(body_json, status=status)

    async def h_otlp_traces(self, request: web.Request) -> web.Response:
        from greptimedb_tpu.servers.trace import TRACE_TABLE, parse_otlp_traces

        try:
            body = await request.read()
        except Exception as e:  # noqa: BLE001
            return web.json_response({"error": f"body: {e}"}, status=400)

        def run():
            cols = parse_otlp_traces(body)
            if not cols:
                return 0
            return _ingest_columns(self.db, TRACE_TABLE, cols,
                                   append_mode=True)

        try:
            n = await self._call(run)
            M_INGEST_ROWS.labels("otlp_traces").inc(n)
            return web.json_response({"partialSuccess": {}})
        except Exception as e:  # noqa: BLE001
            body_json, status = _error_json(e)
            return web.json_response(body_json, status=status)

    async def h_jaeger_services(self, request: web.Request) -> web.Response:
        from greptimedb_tpu.servers.trace import jaeger_services

        try:
            data = await self._call(jaeger_services, self.db)
            return web.json_response({"data": data, "total": len(data),
                                      "limit": 0, "offset": 0, "errors": None})
        except Exception as e:  # noqa: BLE001
            body_json, status = _error_json(e)
            return web.json_response(body_json, status=status)

    async def h_jaeger_operations(self, request: web.Request) -> web.Response:
        from greptimedb_tpu.servers.trace import jaeger_operations

        service = request.query.get("service", "")
        try:
            data = await self._call(jaeger_operations, self.db, service)
            return web.json_response({"data": data, "total": len(data),
                                      "errors": None})
        except Exception as e:  # noqa: BLE001
            body_json, status = _error_json(e)
            return web.json_response(body_json, status=status)

    async def h_jaeger_service_operations(self, request: web.Request) -> web.Response:
        from greptimedb_tpu.servers.trace import jaeger_operations

        service = request.match_info["service"]
        try:
            data = await self._call(jaeger_operations, self.db, service)
            names = [d["name"] for d in data]
            return web.json_response({"data": names, "total": len(names),
                                      "errors": None})
        except Exception as e:  # noqa: BLE001
            body_json, status = _error_json(e)
            return web.json_response(body_json, status=status)

    async def h_jaeger_trace(self, request: web.Request) -> web.Response:
        from greptimedb_tpu.servers.trace import jaeger_trace

        trace_id = request.match_info["trace_id"]
        try:
            data = await self._call(jaeger_trace, self.db, trace_id)
        except Exception as e:  # noqa: BLE001
            body_json, status = _error_json(e)
            return web.json_response(body_json, status=status)
        if not data:
            return web.json_response(
                {"data": [], "errors": [{"code": 404, "msg": "trace not found"}]},
                status=404)
        return web.json_response({"data": data, "errors": None})

    async def h_jaeger_find(self, request: web.Request) -> web.Response:
        from greptimedb_tpu.servers.trace import jaeger_find_traces

        q = request.query

        def run():
            return jaeger_find_traces(
                self.db,
                service=q.get("service"),
                operation=q.get("operation"),
                start_us=int(q["start"]) if "start" in q else None,
                end_us=int(q["end"]) if "end" in q else None,
                min_duration_us=(
                    _parse_go_duration_us(q["minDuration"])
                    if "minDuration" in q else None
                ),
                limit=int(q.get("limit", "20")),
            )

        try:
            data = await self._call(run)
            return web.json_response({"data": data, "errors": None})
        except ValueError as e:
            return web.json_response({"error": str(e)}, status=400)
        except Exception as e:  # noqa: BLE001
            body_json, status = _error_json(e)
            return web.json_response(body_json, status=status)

    async def h_opentsdb_put(self, request: web.Request) -> web.Response:
        """OpenTSDB /api/put (reference src/servers/src/opentsdb.rs): JSON
        datapoints {metric, timestamp, value, tags} — single or array."""
        try:
            payload = json.loads(await request.read())
        except json.JSONDecodeError as e:
            return web.json_response({"error": f"bad json: {e}"}, status=400)
        points = payload if isinstance(payload, list) else [payload]

        def run():
            from collections import defaultdict

            from greptimedb_tpu.errors import InvalidArguments

            per_table: dict[str, list] = defaultdict(list)
            for p in points:
                if not isinstance(p, dict) or "metric" not in p:
                    raise InvalidArguments(f"bad datapoint: {p!r}")
                try:
                    ts = int(p.get("timestamp", 0))
                    value = float(p.get("value", 0))
                except (TypeError, ValueError) as e:
                    raise InvalidArguments(f"bad datapoint {p!r}: {e}") from None
                ts_ms = ts * 1000 if ts < 10**12 else ts  # s or ms heuristic
                tags = {
                    (str(k) + "_tag" if str(k) in ("ts", "val") else str(k)):
                        str(v)
                    for k, v in (p.get("tags") or {}).items()
                }
                # metric names commonly contain dots (sys.cpu.user), which
                # SQL would read as db.table — sanitize to an identifier
                per_table[_safe_table(str(p["metric"]))].append(
                    (tags, value, ts_ms)
                )
            total = 0
            for table, rows in per_table.items():
                tag_names = sorted({k for t, _v, _ts in rows for k in t})
                cols: dict[str, list] = {k: [] for k in tag_names}
                cols["ts"] = []
                cols["val"] = []
                for tags, val, ts in rows:
                    for k in tag_names:
                        cols[k].append(tags.get(k, ""))
                    cols["ts"].append(ts)
                    cols["val"].append(val)
                cols["__tags__"] = tag_names
                cols["__fields__"] = ["val"]
                total += _ingest_columns(self.db, table, cols)
            return total

        try:
            n = await self._call(run)
            M_INGEST_ROWS.labels("opentsdb").inc(n)
            return web.Response(status=204)
        except Exception as e:  # noqa: BLE001
            body_json, status = _error_json(e)
            return web.json_response(body_json, status=status)

    async def h_es_info(self, request: web.Request) -> web.Response:
        return web.json_response({
            "name": "greptimedb-tpu", "cluster_name": "greptimedb",
            "version": {"number": "8.15.0"}, "tagline": "You Know, for Search",
        })

    async def h_es_license(self, request: web.Request) -> web.Response:
        return web.json_response(
            {"license": {"status": "active", "type": "basic"}})

    async def h_es_bulk(self, request: web.Request) -> web.Response:
        """Elasticsearch _bulk emulation for Logstash/Filebeat (reference
        src/servers/src/elasticsearch.rs): NDJSON action/document pairs;
        documents land in a table named after the index."""
        raw = (await request.read()).decode("utf-8")
        default_index = request.match_info.get("index") or request.query.get(
            "index", "es_logs")
        t0 = time.perf_counter()

        def run():
            from collections import defaultdict

            per_table: dict[str, list[dict]] = defaultdict(list)
            index = default_index
            expect_doc = False
            for line in raw.splitlines():
                line = line.strip()
                if not line:
                    continue
                try:
                    doc = json.loads(line)
                except json.JSONDecodeError:
                    # a bad document line must consume its action slot, or
                    # the next action line would be misread as a document
                    expect_doc = False
                    continue
                if not expect_doc:
                    action = next(iter(doc), "")
                    if action in ("index", "create"):
                        index = (doc[action] or {}).get("_index", default_index)
                        expect_doc = True
                    continue
                expect_doc = False
                per_table[_safe_table(index)].append(doc)
            total = 0
            now_ms = int(time.time() * 1000)
            for table, docs in per_table.items():
                rows = []
                for d in docs:
                    ts = now_ms
                    for key in ("@timestamp", "timestamp"):
                        if key in d:
                            try:
                                from greptimedb_tpu.query.parser import (
                                    parse_timestamp_str,
                                )

                                ts = parse_timestamp_str(
                                    str(d[key]).replace("T", " ").rstrip("Z"))
                            except Exception:  # noqa: BLE001
                                pass
                            break
                    rows.append((ts, json.dumps(d)))
                cols = {
                    "__tags__": [], "__fields__": ["doc"],
                    "ts": [r[0] for r in rows],
                    "doc": [r[1] for r in rows],
                }
                total += _ingest_columns(self.db, table, cols,
                                         append_mode=True)
            return total

        try:
            n = await self._call(run)
            M_INGEST_ROWS.labels("elasticsearch").inc(n)
            took = int((time.perf_counter() - t0) * 1000)
            return web.json_response({"took": took, "errors": False,
                                      "items": []})
        except Exception as e:  # noqa: BLE001
            body_json, status = _error_json(e)
            return web.json_response(body_json, status=status)

    async def h_splunk_hec(self, request: web.Request) -> web.Response:
        """Splunk HTTP Event Collector (reference src/servers/src/http/
        splunk.rs): concatenated JSON events {time, event, fields,
        sourcetype}."""
        raw = (await request.read()).decode("utf-8")

        def run():
            from greptimedb_tpu.errors import InvalidArguments

            dec = json.JSONDecoder()
            events = []
            pos = 0
            s = raw.strip()
            while pos < len(s):
                while pos < len(s) and s[pos].isspace():
                    pos += 1
                if pos >= len(s):
                    break
                try:
                    obj, end = dec.raw_decode(s, pos)
                except json.JSONDecodeError as e:
                    raise InvalidArguments(f"bad HEC payload: {e}") from None
                events.append(obj)
                pos = end
            rows = []
            for e in events:
                if not isinstance(e, dict):
                    continue
                t = e.get("time")
                ts_ms = (
                    int(float(t) * 1000) if t is not None
                    else int(time.time() * 1000)
                )
                ev = e.get("event")
                line = ev if isinstance(ev, str) else json.dumps(ev)
                rows.append((str(e.get("sourcetype", "")), line, ts_ms))
            if not rows:
                return 0
            cols = {
                "__tags__": ["sourcetype"], "__fields__": ["event"],
                "sourcetype": [r[0] for r in rows],
                "ts": [r[2] for r in rows],
                "event": [r[1] for r in rows],
            }
            return _ingest_columns(self.db, "splunk_events", cols,
                                   append_mode=True)

        try:
            n = await self._call(run)
            M_INGEST_ROWS.labels("splunk").inc(n)
            return web.json_response({"text": "Success", "code": 0})
        except Exception as e:  # noqa: BLE001
            body_json, status = _error_json(e)
            return web.json_response(body_json, status=status)

    def _pipelines(self):
        from greptimedb_tpu.servers.pipeline import PipelineManager

        return PipelineManager(self.db)

    async def h_pipeline_upsert(self, request: web.Request) -> web.Response:
        name = request.match_info["name"]
        body = (await request.read()).decode("utf-8")
        try:
            pipe = await self._call(self._pipelines().upsert, name, body)
            return web.json_response(
                {"name": name, "version": pipe.version})
        except Exception as e:  # noqa: BLE001
            body_json, status = _error_json(e)
            return web.json_response(body_json, status=status)

    async def h_pipeline_delete(self, request: web.Request) -> web.Response:
        name = request.match_info["name"]
        ok = await self._call(self._pipelines().delete, name)
        if not ok:
            return web.json_response({"error": f"pipeline {name} not found"},
                                     status=404)
        return web.json_response({"name": name})

    async def h_pipeline_list(self, request: web.Request) -> web.Response:
        out = await self._call(self._pipelines().list)
        return web.json_response(
            {"pipelines": [{"name": n, "version": v} for n, v in out]})

    async def h_ingest(self, request: web.Request) -> web.Response:
        """Log ingestion through a pipeline (reference /v1/ingest +
        http/event.rs): body is NDJSON or a JSON array of objects; the
        pipeline shapes rows into table columns."""
        table = request.query.get("table")
        pname = request.query.get("pipeline_name")
        if not table or not pname:
            return web.json_response(
                {"error": "table and pipeline_name query params required"},
                status=400)
        raw = (await request.read()).decode("utf-8")

        def run():
            from greptimedb_tpu.errors import InvalidArguments

            rows: list[dict] = []
            stripped = raw.strip()
            if stripped.startswith("["):
                try:
                    parsed = json.loads(stripped)
                except json.JSONDecodeError as e:
                    raise InvalidArguments(f"bad json body: {e}") from None
                rows = [r for r in parsed if isinstance(r, dict)]
            else:
                for line in stripped.splitlines():
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        parsed = json.loads(line)
                    except json.JSONDecodeError:
                        parsed = None
                    rows.append(
                        parsed if isinstance(parsed, dict)
                        else {"message": line}
                    )
            pipe = self._pipelines().get(pname)
            cols = pipe.run(rows)
            if not cols["ts"]:
                return 0
            return _ingest_columns(self.db, table, cols, append_mode=True)

        try:
            n = await self._call(run)
            M_INGEST_ROWS.labels("pipeline").inc(n)
            return web.json_response({"rows": n})
        except Exception as e:  # noqa: BLE001
            body_json, status = _error_json(e)
            return web.json_response(body_json, status=status)

    async def h_health(self, request: web.Request) -> web.Response:
        return web.json_response({})

    async def h_metrics(self, request: web.Request) -> web.Response:
        return web.Response(text=telemetry.REGISTRY.render(),
                            content_type="text/plain")

    async def h_config(self, request: web.Request) -> web.Response:
        cfg = {
            "data_home": self.db.data_home,
            "http": {"addr": f"{self.host}:{self.port}"},
            "version": "greptimedb-tpu-0.1.0",
        }
        return web.Response(text=json.dumps(cfg, indent=2),
                            content_type="text/plain")

    async def h_dashboard(self, request: web.Request) -> web.Response:
        """Embedded web UI (reference src/servers/src/http.rs:1252)."""
        from greptimedb_tpu.servers.dashboard import DASHBOARD_HTML

        return web.Response(text=DASHBOARD_HTML, content_type="text/html")

    async def h_status(self, request: web.Request) -> web.Response:
        import jax

        payload = {
            "version": "greptimedb-tpu-0.1.0",
            "devices": [str(d) for d in jax.devices()],
            "tables": len(self.db.catalog.list_tables(self.db.current_db)),
            "memory": self.db.memory.usage(),
        }
        ft = getattr(getattr(self.db, "engine", None), "executor", None)
        ft = getattr(ft, "fulltext_cache", None)
        if ft is not None and len(ft):
            payload["fulltext"] = ft.stats()
        return web.json_response(payload)

    async def h_slo(self, request: web.Request) -> web.Response:
        """Closed-loop SLO observatory (ISSUE 18): per-(tenant, class,
        protocol) sketch status, firing burn-rate alerts, and the idle
        economy's consumer ledgers — the same rows as
        ``information_schema.slo_status``."""
        slo = self.db.slo
        payload = {
            "enabled": True,
            "status": slo.status_rows(),
            "alerts": slo.alerts(),
            "idle": self.db.idle_economy.consumers(),
        }
        return web.json_response(payload)

    async def h_promql(self, request: web.Request) -> web.Response:
        """Greptime-native PromQL endpoint: query/start/end/step params,
        greptime JSON envelope output (reference /v1/promql)."""
        t0 = time.perf_counter()
        ctx = _request_trace_context(request)
        try:
            query = await self._param(request, "query")
            start = _parse_prom_time(await self._param(request, "start", "0"))
            end = _parse_prom_time(await self._param(request, "end", "0"))
            step = _parse_prom_duration(await self._param(request, "step", "60"))
            res, steps = await self._eval_promql(query, start, end, step,
                                                 trace_ctx=ctx,
                                                 tenant=self._tenant(request))
            vals = np.asarray(res.values, dtype=np.float64)
            label_keys = sorted({k for lab in res.labels for k in lab})
            rows = []
            for s, lab in enumerate(res.labels):
                for t in range(len(steps)):
                    v = vals[s, t]
                    if not np.isnan(v):
                        rows.append(
                            [str(lab.get(k, "")) for k in label_keys]
                            + [int(steps[t]), float(v)]
                        )
            qr = QueryResult(label_keys + ["ts", "val"], rows)
            return web.json_response(_result_to_json(qr, t0),
                                     headers=_trace_headers(ctx))
        except Exception as e:  # noqa: BLE001
            body, status = _error_json(e)
            return web.json_response(body, status=status)

    # ---- lifecycle -----------------------------------------------------
    async def h_log_level(self, request):
        """Dynamic log level (reference src/servers/src/http/dyn_log.rs:
        POST /debug/log_level with the new level in the body)."""
        import logging

        root = logging.getLogger()
        if request.method in ("POST", "PUT"):
            level = (await request.text()).strip().upper()
            if level not in ("DEBUG", "INFO", "WARNING", "WARN", "ERROR",
                             "CRITICAL"):
                return web.json_response(
                    {"error": f"unknown level {level!r}"}, status=400)
            root.setLevel("WARNING" if level == "WARN" else level)
        return web.json_response(
            {"level": logging.getLevelName(root.level)})

    async def h_prof_mem(self, request):
        """Heap + HBM memory profile (reference
        src/servers/src/http/mem_prof.rs, which dumps a jemalloc heap
        profile; the python analog is tracemalloc).  Actions:

        - ``?action=start``: activate tracemalloc (``frames=N`` stack
          depth, default 1) and snapshot the baseline;
        - ``?action=snapshot`` (default): top-N allocation sites
          (``top=N``, default 20) and, once a baseline exists, the
          DIFF against it (what grew since start / the last snapshot);
        - ``?action=stop``: deactivate tracing and drop the baseline.

        Every response also reports the device side: per-workload
        used/quota/peak bytes from the workload-manager budgets
        (utils/memory.py) with HBM-kind workloads summed separately —
        the resident grids, layout caches and flow state live there,
        invisible to any host allocator profile."""
        import tracemalloc

        action = request.query.get("action", "snapshot")
        try:
            top_n = max(1, min(int(request.query.get("top", "20")), 100))
            frames = max(1, min(int(request.query.get("frames", "1")), 32))
        except ValueError:
            return web.json_response(
                {"error": "top/frames must be integers"}, status=400)

        def workloads():
            usage = self.db.memory.usage()
            hbm = sum(w["used_bytes"] for w in usage.values()
                      if w["kind"] == "hbm")
            return {"workloads": usage, "hbm_used_bytes": hbm}

        if action == "start":
            if not tracemalloc.is_tracing():
                tracemalloc.start(frames)
            self._mem_baseline = tracemalloc.take_snapshot()
            return web.json_response(
                {"tracing": True, "action": "start", **workloads()})
        if action == "stop":
            self._mem_baseline = None
            if tracemalloc.is_tracing():
                tracemalloc.stop()
            return web.json_response(
                {"tracing": False, "action": "stop", **workloads()})
        if action != "snapshot":
            return web.json_response(
                {"error": f"unknown action {action!r}"}, status=400)
        payload = {"tracing": tracemalloc.is_tracing(), **workloads()}
        if tracemalloc.is_tracing():
            snap = tracemalloc.take_snapshot()
            traced, peak = tracemalloc.get_traced_memory()
            payload["traced_bytes"] = traced
            payload["traced_peak_bytes"] = peak
            payload["top"] = [
                {"site": str(s.traceback), "size_bytes": s.size,
                 "count": s.count}
                for s in snap.statistics("lineno")[:top_n]
            ]
            base = getattr(self, "_mem_baseline", None)
            if base is not None:
                payload["diff"] = [
                    {"site": str(s.traceback), "size_diff": s.size_diff,
                     "count_diff": s.count_diff}
                    for s in snap.compare_to(base, "lineno")[:top_n]
                ]
            self._mem_baseline = snap
        return web.json_response(payload)

    async def h_prof_cpu(self, request):
        """Statistical CPU profile (reference src/servers/src/http/pprof.rs
        samples for N seconds and returns a report): samples every thread's
        stack at ~100Hz for ?seconds=N (default 2), returns aggregated
        frame counts, hottest first."""
        import asyncio
        import collections as _collections
        import sys as _sys
        import time as _time
        import traceback as _traceback

        try:
            seconds = min(float(request.query.get("seconds", "2")), 30.0)
        except ValueError:
            return web.json_response(
                {"error": "seconds must be a number"}, status=400)
        if getattr(self, "_profiling", False):
            return web.json_response(
                {"error": "a profile is already running"}, status=429)
        self._profiling = True

        def sample():
            counts: "_collections.Counter[str]" = _collections.Counter()
            deadline = _time.time() + seconds
            nsamples = 0
            while _time.time() < deadline:
                for frames in _sys._current_frames().values():
                    stack = _traceback.extract_stack(frames)
                    if stack:
                        f = stack[-1]
                        counts[f"{f.filename}:{f.lineno} {f.name}"] += 1
                nsamples += 1
                _time.sleep(0.01)
            return counts, nsamples

        try:
            counts, nsamples = await asyncio.get_event_loop(
            ).run_in_executor(None, sample)
        finally:
            self._profiling = False
        top = counts.most_common(50)
        body = "\n".join(
            f"{c:6d} {frame}" for frame, c in top
        )
        return web.Response(
            text=f"samples={nsamples} interval=10ms\n{body}\n",
            content_type="text/plain")

    # start()/stop() come from ThreadedAiohttpApp


def _parse_prom_time(raw) -> float:
    if raw is None:
        raise GreptimeError("missing time parameter",
                            code=StatusCode.INVALID_ARGUMENTS)
    try:
        return float(raw)
    except (TypeError, ValueError):
        pass
    from greptimedb_tpu.query.parser import parse_timestamp_str

    return parse_timestamp_str(str(raw).replace("T", " ").rstrip("Z")) / 1000.0


def _parse_prom_duration(raw) -> float:
    try:
        return float(raw)
    except (TypeError, ValueError):
        from greptimedb_tpu.query.parser import parse_interval_str

        return parse_interval_str(str(raw)) / 1000.0


def _parse_go_duration_us(raw: str) -> int:
    """Go-style duration (Jaeger minDuration): '100ms', '2s', '50us', '1m'."""
    s = raw.strip().lower()
    for suffix, mult in (("us", 1), ("µs", 1), ("ms", 1000),
                         ("m", 60_000_000), ("s", 1_000_000), ("h", 3_600_000_000)):
        if s.endswith(suffix):
            return int(float(s[: -len(suffix)]) * mult)
    return int(float(s))  # bare number: microseconds


def _safe_table(name: str) -> str:
    out = "".join(ch if (ch.isalnum() or ch == "_") else "_" for ch in name)
    return out or "es_logs"


# serializes catalog/schema mutation (table auto-create, alter-on-demand,
# flow notification) across the ingest pool's workers — region WRITES run
# outside it under their own per-region locks, so the common steady-state
# path (schema already in place) takes this only for two dict probes
_INGEST_DDL_LOCK = threading.RLock()


def _ingest_field_type(values):
    """Field column → ConcreteDataType; dtype-dispatch for the vectorized
    (ndarray/DictColumn) columns, first-non-null scan for legacy lists."""
    from greptimedb_tpu.datatypes.batch import DictColumn
    from greptimedb_tpu.datatypes.types import ConcreteDataType

    if isinstance(values, DictColumn):
        return ConcreteDataType.STRING
    if isinstance(values, np.ndarray) and values.dtype != object:
        if values.dtype == np.bool_:
            return ConcreteDataType.BOOL
        if np.issubdtype(values.dtype, np.integer):
            return ConcreteDataType.INT64
        if np.issubdtype(values.dtype, np.floating):
            return ConcreteDataType.FLOAT64
    for v in values:
        if isinstance(v, (bool, np.bool_)):
            return ConcreteDataType.BOOL
        if isinstance(v, str):
            return ConcreteDataType.STRING
        if isinstance(v, (float, np.floating)):
            return ConcreteDataType.FLOAT64
        if isinstance(v, (int, np.integer)):
            return ConcreteDataType.INT64
    return ConcreteDataType.FLOAT64


def _ingest_columns(db, table: str, cols: dict,
                    append_mode: bool = False) -> int:
    """Auto-creating ingest (reference Inserter auto table creation,
    src/operator/src/insert.rs:178-304): create the table from the first
    batch's shape, add columns on demand, then write.  ``append_mode``
    creates log/trace-style tables that keep EVERY row (no (series, ts)
    dedup — reference CREATE TABLE WITH (append_mode='true')).

    Columns may be legacy Python lists or vectorized ndarray/DictColumn
    batches; the write path never materializes per-row objects for the
    latter (partition routing slices by index at C level).  Safe for
    concurrent callers: schema setup serializes on ``_INGEST_DDL_LOCK``,
    row appends on each region's own write lock."""
    from greptimedb_tpu.datatypes.batch import DictColumn
    from greptimedb_tpu.datatypes.schema import ColumnSchema, Schema
    from greptimedb_tpu.datatypes.types import ConcreteDataType, SemanticType
    from greptimedb_tpu.query.ast import AlterTable, ColumnDef

    tag_names = cols.pop("__tags__", [])
    field_names = cols.pop("__fields__", [])
    # raw wire bytes usable as the WAL payload verbatim (arrow bulk);
    # only valid when the whole batch lands in ONE region intact
    wire_ipc = cols.pop("__wire_ipc__", None)
    n = len(cols["ts"])
    field_type = _ingest_field_type

    dbname, name = db._split_name(table)
    with _INGEST_DDL_LOCK:
        if not db.catalog.table_exists(dbname, name):
            defs = [ColumnSchema(t, ConcreteDataType.STRING, SemanticType.TAG)
                    for t in tag_names]
            defs.append(ColumnSchema(
                "ts", ConcreteDataType.TIMESTAMP_MILLISECOND,
                SemanticType.TIMESTAMP, nullable=False))
            defs += [ColumnSchema(f, field_type(cols[f]), SemanticType.FIELD)
                     for f in field_names]
            info = db.catalog.create_table(
                dbname, name, Schema(tuple(defs)),
                options={"append_mode": "true"} if append_mode else None,
                if_not_exists=True)
            if info is not None:
                opts = None
                if append_mode:
                    import dataclasses as _dc

                    opts = _dc.replace(db.regions.default_options,
                                       append_mode=True)
                db.regions.create_region(info.region_ids[0], info.schema,
                                         options=opts)
        else:
            info = db.catalog.get_table(dbname, name)
            missing_tags = [t for t in tag_names
                            if not info.schema.has_column(t)]
            if missing_tags:
                # online tag addition (reference alter-on-demand,
                # src/operator/src/insert.rs): existing series extend their
                # key with the empty-string label — same machinery as the
                # metric engine's label growth
                tag_regions = db._regions_of(f"{dbname}.{name}")
                for region in tag_regions:
                    for t in missing_tags:
                        region.add_tag_column(t)
                info.schema = tag_regions[0].schema
                db.catalog.update_table(info)
            for f in field_names:
                if not info.schema.has_column(f):
                    db.execute_statement(AlterTable(
                        f"{dbname}.{name}", "add_column",
                        column=ColumnDef(f, field_type(cols[f]).value),
                    ))
                    info = db.catalog.get_table(dbname, name)
        regions = db._regions_of(f"{dbname}.{name}")
    if len(regions) == 1:
        regions[0].write(cols, wire_payload=wire_ipc)
    else:
        # partition routing, ONCE per batch (same as SQL INSERT; skipping
        # it would dump all rows into region 0 and break cross-region
        # dedup/DELETE): evaluate the rule over materialized key columns,
        # then slice every column per target region by index — fancy
        # indexing / DictColumn.take, no per-row Python loop
        from greptimedb_tpu.parallel.partition import split_rows

        rule = db._partition_rule(f"{dbname}.{name}")
        # the rule only reads its key columns — materializing every
        # column to per-row objects here would undo the vectorized
        # parse's zero-object discipline on exactly the sharded path
        # (split_rows boxes the key columns itself)
        cols_np = {
            c: (cols[c].materialize() if isinstance(cols[c], DictColumn)
                else cols[c])
            for c in (rule.columns or list(cols))
            if c in cols
        }
        parts = split_rows(rule, cols_np, n)
        for pidx, row_idx in parts.items():
            idx = np.asarray(row_idx, dtype=np.int64)
            sub = {}
            for c, v in cols.items():
                if isinstance(v, DictColumn):
                    sub[c] = v.take(idx)
                elif isinstance(v, np.ndarray):
                    sub[c] = v[idx]
                else:
                    sub[c] = [v[i] for i in row_idx]
            regions[pidx].write(sub)
    # hot-tail grid catch-up: freshly acked rows scatter into the
    # resident grid's not-yet-covered tail right now (when one is
    # resident and the delta is worth a dispatch) — the next query sees
    # them without any flush/rebuild
    cache = getattr(db, "cache", None)
    if cache is not None:
        for region in regions:
            cache.extend_hot_tail(region)
    if db.flow_engine.flows:
        with _INGEST_DDL_LOCK:
            appendable = all(
                getattr(r, "last_write_appendable", True) for r in regions
            )
            db.flow_engine.on_write(name, cols["ts"], data=cols,
                                    appendable=appendable)
            db.flow_engine.run_all()
    return n
