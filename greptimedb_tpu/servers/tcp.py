"""Shared lifecycle for threaded asyncio TCP servers (MySQL/Postgres wire).

One place for the loop/thread/executor boilerplate — including propagating
bind errors out of the daemon thread (a busy port must fail start()
immediately with the real errno, not a generic timeout).
"""

from __future__ import annotations

import asyncio
import threading
from concurrent.futures import ThreadPoolExecutor

from greptimedb_tpu.utils.telemetry import REGISTRY
from greptimedb_tpu.utils.tracing import extract_sql_trace_context

# same histogram object as servers/http.py's M_PROTOCOL_QUERY (the
# registry dedupes by name): the wire servers label it mysql/postgres
M_PROTOCOL_QUERY = REGISTRY.histogram(
    "greptime_protocol_query_duration_seconds",
    "Query latency by wire protocol", ("protocol",)
)


class ThreadedTcpServer:
    name = "greptime-tcp"
    protocol = "tcp"  # per-protocol latency label (mysql/postgres)

    def __init__(self, db, host: str, port: int):
        self.db = db
        self.host = host
        self.port = port
        self._thread: threading.Thread | None = None
        self._loop: asyncio.AbstractEventLoop | None = None
        self._started = threading.Event()
        self._start_error: BaseException | None = None
        # ONE worker: ingest/read handlers call region.write / scan paths
        # that are unsynchronized by design (mito2-style single worker per
        # region) and rely on this pool for serialization. Registry-only
        # statements (KILL, SHOW PROCESSLIST) bypass the pool entirely —
        # see db.try_fast_sql at the protocol call sites.  The pool
        # carries only BLOCKING submit calls (the scheduler owns execution
        # order and the db lock owns correctness), so it is wide enough
        # to let concurrent connections queue into the scheduler instead
        # of serializing in front of it.
        self._db_executor = ThreadPoolExecutor(
            max_workers=16, thread_name_prefix=f"{self.name}-db"
        )

    async def _handle(self, reader, writer) -> None:  # pragma: no cover
        raise NotImplementedError

    def timed_sql_in_db(self, query, dbname, timezone=None, user=""):
        """db.sql_in_db with this protocol's latency observation — the
        run_in_executor entry every wire statement goes through.  MySQL/
        PostgreSQL have no request headers, so trace context rides in a
        leading SQL comment (sqlcommenter convention,
        ``/* traceparent='00-…-…-01' */ SELECT …``) and seeds the span
        tree exactly like the HTTP ``traceparent`` header; this runs ON
        a db-executor thread.  The statement submits to the scheduler:
        the connection's authenticated ``user`` is its tenant identity
        for admission, and the scheduler's worker installs the trace
        context."""
        ctx = extract_sql_trace_context(query)
        with M_PROTOCOL_QUERY.labels(self.protocol).time():
            return self.db.scheduler.submit_session(
                query, dbname, timezone,
                tenant=user or "default", client=self.protocol,
                trace_ctx=ctx, protocol=self.protocol)

    def start(self) -> None:
        def run_loop():
            loop = asyncio.new_event_loop()
            asyncio.set_event_loop(loop)
            self._loop = loop
            try:
                server = loop.run_until_complete(
                    asyncio.start_server(self._handle, self.host, self.port)
                )
            except BaseException as e:  # noqa: BLE001
                self._start_error = e
                self._started.set()
                loop.close()
                return
            if self.port == 0:
                self.port = server.sockets[0].getsockname()[1]
            self._started.set()
            loop.run_forever()
            server.close()
            loop.run_until_complete(server.wait_closed())
            loop.close()

        self._thread = threading.Thread(target=run_loop, daemon=True,
                                        name=self.name)
        self._thread.start()
        if not self._started.wait(timeout=10):
            raise RuntimeError(f"{self.name} failed to start (timeout)")
        if self._start_error is not None:
            raise RuntimeError(
                f"{self.name} failed to start: {self._start_error}"
            ) from self._start_error

    def stop(self) -> None:
        if self._loop is not None and self._loop.is_running():
            self._loop.call_soon_threadsafe(self._loop.stop)
        if self._thread is not None:
            self._thread.join(timeout=5)
        self._db_executor.shutdown(wait=True, cancel_futures=True)
