"""Distributed frontend: stateless SQL router over remote datanodes.

The process-split analog of the reference frontend Instance
(src/frontend/src/instance.rs:917) with the MergeScan execution model
(src/query/src/dist_plan/merge_scan.rs:210,335): DDL creates regions on
datanodes and records routes; INSERT splits rows by partition rule and
ships per-region Flight do_put batches; SELECT pushes the commutative
partial query (rpc/partial.py) to every datanode hosting the table and
merges partial states on the frontend — or, for non-decomposable
queries, pulls filtered rows into a local staging instance and finishes
with the full local engine (the reference's "rest of the plan executes
on the frontend" path).
"""

from __future__ import annotations

import re
import time

import numpy as np
import pyarrow.flight as fl

from greptimedb_tpu.errors import GreptimeError, Unsupported
from greptimedb_tpu.meta.catalog import CatalogManager
from greptimedb_tpu.meta.failure_detector import PhiAccrualFailureDetector
from greptimedb_tpu.meta.kv import KvBackend, MemoryKv
from greptimedb_tpu.query.ast import CreateTable, Insert, Select
from greptimedb_tpu.query.engine import QueryResult, SortVal
from greptimedb_tpu.query.exprs import TableContext
from greptimedb_tpu.query.parser import parse_sql
from greptimedb_tpu.rpc.client import RemoteDatanode
from greptimedb_tpu.rpc.partial import merge_partials, split_partial
from greptimedb_tpu.utils.chaos import ChaosError
from greptimedb_tpu.utils.telemetry import REGISTRY

M_ROUTE_RETRY = REGISTRY.counter(
    "greptime_frontend_route_retry_total",
    "Requests retried after a route refresh (stale route / dead node)",
    labels=("op",),
)
M_READ_ROUTE = REGISTRY.counter(
    "greptime_frontend_read_route_total",
    "Read routing decisions under the read preference",
    labels=("target",),
)

# errors that plausibly mean "my route is stale or the node just died":
# worth ONE route refresh + retry (the transport-level retry inside
# DatanodeClient already handled transient blips on a live route)
_STALE_ROUTE_MSG = re.compile(
    r"no route|not open on node|is down|not leader|lease expired|chaos"
)


def _route_retryable(e: Exception) -> bool:
    if isinstance(e, (ChaosError, ConnectionError)):
        return True
    if isinstance(e, (fl.FlightUnavailableError, fl.FlightTimedOutError)):
        return True
    if isinstance(e, (fl.FlightError, GreptimeError)):
        return bool(_STALE_ROUTE_MSG.search(str(e)))
    return False


class DistFrontend:
    def __init__(self, kv: KvBackend | None = None, db: str = "public"):
        self.kv = kv or MemoryKv()
        self.catalog = CatalogManager(self.kv)
        if not self.catalog.database_exists(db):
            self.catalog.create_database(db, if_not_exists=True)
        self.db = db
        self.datanodes: dict[int, RemoteDatanode] = {}
        self._rr = 0  # round-robin cursor for region placement
        self.timezone = "UTC"
        # failure detectors over frontend-observed traffic: fed by
        # note_heartbeat (tests/metasrv embedding drive it explicitly;
        # serve_frontend ticks it from node health).  A node with NO
        # observations is presumed alive — detectors only ever REMOVE
        # candidates from placement, never queries from routing.
        self.detectors: dict[int, PhiAccrualFailureDetector] = {}
        # bounded-staleness read contract (reference read-preference):
        # "follower" routes SELECTs to a replica whose published
        # replication lag is within max_staleness_ms, else the leader
        self.read_preference = "leader"
        self.max_staleness_ms = 5_000.0
        self.clock_ms = lambda: time.time() * 1000.0

    # ---- membership ----------------------------------------------------
    def add_datanode(self, node_id: int, address: str) -> RemoteDatanode:
        dn = RemoteDatanode(node_id, address)
        self.datanodes[node_id] = dn
        self.detectors.setdefault(node_id, PhiAccrualFailureDetector())
        return dn

    def note_heartbeat(self, node_id: int, now_ms: float | None = None) -> None:
        """Feed the node's failure detector (any observed sign of life)."""
        det = self.detectors.get(node_id)
        if det is not None:
            det.heartbeat(self.clock_ms() if now_ms is None else now_ms)

    def _node_dead(self, node_id: int) -> bool:
        det = self.detectors.get(node_id)
        if det is None or det._last_heartbeat_ms is None:
            return False  # no evidence either way: usable
        return not det.is_available(self.clock_ms())

    def close(self) -> None:
        for dn in self.datanodes.values():
            dn.client.close()

    # ---- routes --------------------------------------------------------
    def set_region_route(self, region_id: int, node_id: int) -> None:
        self.kv.put_json(f"__meta/route/region/{region_id}",
                         {"node": node_id})

    def region_route(self, region_id: int) -> int | None:
        rec = self.kv.get_json(f"__meta/route/region/{region_id}")
        return None if rec is None else rec["node"]

    def _follower_node(self, region_id: int, leader: int) -> int:
        """Bounded-staleness read routing: a live follower whose published
        lag is inside the contract serves the read; anything else falls
        back to the leader (metasrv heartbeats publish lag into the kv
        follower routes — meta/cluster.py _note_follower_lag)."""
        rec = self.kv.get_json(f"__meta/route/followers/{region_id}")
        now = self.clock_ms()
        for n_str, meta in (rec or {}).get("nodes", {}).items():
            node = int(n_str)
            if node not in self.datanodes or self._node_dead(node):
                continue
            lag = meta.get("lag_ms")
            if lag is None:
                continue  # never synced: no freshness claim at all
            # the record itself ages: a metasrv that stopped publishing
            # (died, partitioned) must not leave a frozen "lag 10ms"
            # snapshot routing reads forever — the replica's worst-case
            # staleness is its published lag PLUS the record's age
            age = max(now - meta.get("ts", now), 0.0)
            if lag + age <= self.max_staleness_ms:
                M_READ_ROUTE.labels("follower").inc()
                return node
        M_READ_ROUTE.labels("leader").inc()
        return leader

    # ---- SQL entry -----------------------------------------------------
    def sql(self, query: str) -> QueryResult:
        stmts = parse_sql(query)
        res = QueryResult([], [])
        for stmt in stmts:
            if isinstance(stmt, CreateTable):
                res = self._create_table(stmt)
            elif isinstance(stmt, Insert):
                res = self._insert(stmt)
            elif isinstance(stmt, Select):
                if len(stmts) > 1:
                    raise Unsupported(
                        "multi-statement scripts with SELECT on the "
                        "distributed frontend"
                    )
                res = self._select(stmt, query)
            else:
                raise Unsupported(
                    f"distributed frontend: {type(stmt).__name__}"
                )
        return res

    # ---- DDL -----------------------------------------------------------
    def _create_table(self, stmt: CreateTable) -> QueryResult:
        from greptimedb_tpu.standalone import schema_from_create

        schema = schema_from_create(stmt)
        info = self.catalog.create_table(
            self.db, stmt.name, schema,
            engine=stmt.engine,
            options=stmt.options,
            partition_exprs=stmt.partitions,
            partition_columns=stmt.partition_columns,
            num_regions=max(len(stmt.partitions), 1),
            if_not_exists=stmt.if_not_exists,
        )
        if info is None:  # IF NOT EXISTS on an existing table
            return QueryResult([], [])
        if not self.datanodes:
            raise GreptimeError("no datanodes registered")
        # placement skips nodes the failure detector considers dead — a
        # region placed on a dying node would fail over immediately
        node_ids = [n for n in sorted(self.datanodes)
                    if not self._node_dead(n)]
        if not node_ids:
            raise GreptimeError("no alive datanodes for region placement")
        from greptimedb_tpu.meta.cluster import mint_epoch

        for rid in info.region_ids:
            node = node_ids[self._rr % len(node_ids)]
            self._rr += 1
            # the FIRST leadership grant mints an epoch too (ISSUE 15):
            # without it the original leader runs unfenced, and after a
            # phi-false-positive failover its epoch-less writes would
            # bypass the new leader's fence
            self.datanodes[node].handle_instruction(
                {"kind": "open_region", "region_id": rid, "role": "leader",
                 "schema": schema.to_dict(),
                 "epoch": mint_epoch(self.kv, rid)}, 0.0,
            )
            self.set_region_route(rid, node)
        return QueryResult([], [])

    # ---- DML -----------------------------------------------------------
    def _partition_rule(self, info):
        from greptimedb_tpu.parallel.partition import PartitionRule

        if info.partition_exprs:
            return PartitionRule.from_sql(info.partition_columns,
                                          info.partition_exprs)
        return PartitionRule.hash_rule(
            len(info.region_ids), [c.name for c in info.schema.tag_columns]
        )

    def _insert(self, stmt: Insert) -> QueryResult:
        from greptimedb_tpu.parallel.partition import split_rows
        from greptimedb_tpu.standalone import insert_rows_to_columns

        info = self.catalog.get_table(self.db, stmt.table)
        schema = info.schema
        columns, data = insert_rows_to_columns(stmt, schema, self.timezone)
        n = len(stmt.rows)
        if len(info.region_ids) == 1:
            routed = {0: np.arange(n)}
        else:
            rule = self._partition_rule(info)
            cols_np = {c: np.asarray(v, dtype=object)
                       for c, v in data.items()}
            routed = split_rows(rule, cols_np, n)
        for pidx, row_idx in routed.items():
            rid = info.region_ids[pidx]
            chunk = {c: [data[c][i] for i in row_idx] for c in columns}
            self._write_region(rid, chunk)
        return QueryResult([], [], affected_rows=n)

    def _write_region(self, rid: int, chunk: dict) -> None:
        """Route-aware write: a failure that smells like a stale route
        (node died, region moved, lease fenced) re-reads the route from
        kv — failover may have swapped it — and retries ONCE.  Region
        upsert semantics keep an ambiguous first attempt idempotent."""

        def ship():
            node = self.region_route(rid)
            if node is None or node not in self.datanodes:
                raise GreptimeError(f"no route for region {rid}")
            self.datanodes[node].client.write(rid, chunk)
            self.note_heartbeat(node)

        try:
            ship()
        except Exception as e:  # noqa: BLE001 — filtered just below
            if not _route_retryable(e):
                raise
            M_ROUTE_RETRY.labels("write").inc()
            ship()

    # ---- reads ---------------------------------------------------------
    def _node_regions(self, info, for_read: bool = False) -> dict[int, list[int]]:
        """region ids of this table grouped by hosting datanode."""
        out: dict[int, list[int]] = {}
        for rid in info.region_ids:
            node = self.region_route(rid)
            if node is None:
                raise GreptimeError(f"no route for region {rid}")
            if for_read and self.read_preference == "follower":
                node = self._follower_node(rid, node)
            out.setdefault(node, []).append(rid)
        return out

    def _select(self, sel: Select, raw_sql: str) -> QueryResult:
        # one route-refresh retry: routes re-read from kv inside the
        # attempt, so a failover that swapped them mid-flight is picked up
        try:
            return self._select_attempt(sel, raw_sql)
        except Exception as e:  # noqa: BLE001 — filtered just below
            if not _route_retryable(e):
                raise
            M_ROUTE_RETRY.labels("select").inc()
            return self._select_attempt(sel, raw_sql)

    def _select_attempt(self, sel: Select, raw_sql: str) -> QueryResult:
        if sel.table is None:
            raise Unsupported("tableless SELECT on the distributed frontend")
        base = sel
        has_joins = bool(sel.joins)
        while (isinstance(base, Select)
               and getattr(base, "from_subquery", None) is not None):
            base = base.from_subquery
            if isinstance(base, Select) and base.joins:
                has_joins = True
        if base is not sel:
            # derived table (nested aggregates over RANGE subqueries):
            # pull the BASE table's rows exactly like a raw select — the
            # innermost WHERE still pushes its time range into the remote
            # scan — and run the WHOLE statement on the staging instance,
            # whose standalone engine owns from_subquery semantics.  A
            # non-Select inner (set operation) has no single base table;
            # a JOIN anywhere in the chain refuses BEFORE staging pulls a
            # full remote scan only to fail locally.
            if (not isinstance(base, Select) or base.table is None
                    or has_joins):
                raise Unsupported(
                    "distributed derived table without a single base table")
            info = self.catalog.get_table(self.db, base.table)
            by_node = self._node_regions(info, for_read=True)
            return self._select_raw(base, info, by_node, raw_sql)
        info = self.catalog.get_table(self.db, sel.table)
        by_node = self._node_regions(info, for_read=True)
        ts_col = (info.schema.time_index.name
                  if info.schema.time_index is not None else None)
        plan = split_partial(sel, ts_column=ts_col)
        if plan is not None:
            # MergeScan fast path: the frontend derives the partial split
            # ONCE, encodes it ONCE (plan codec, substrait analog), and
            # every datanode executes exactly this plan
            from greptimedb_tpu.query.plancodec import encode_plan

            doc = encode_plan(plan.partial_select)
            parts = []
            for node, rids in by_node.items():
                table = self.datanodes[node].client.query_plan(
                    doc, sel.table, rids, timezone=self.timezone,
                )
                self.note_heartbeat(node)
                parts.append({
                    name: table.column(name).to_pylist()
                    for name in table.column_names
                    if name != "__empty__"
                })
            names, rows = merge_partials(plan, parts)
            return self._shape(sel, QueryResult(names, rows))
        return self._select_raw(sel, info, by_node, raw_sql)

    def _select_raw(self, sel: Select, info, by_node,
                    raw_sql: str) -> QueryResult:
        """Pull filtered rows into a local staging instance, finish
        locally.  The time-index range from the WHERE clause is pushed
        into the remote scan (reference scan-hint pruning); the full WHERE
        re-applies locally over the staged rows."""
        from greptimedb_tpu.query.planner import extract_time_range
        from greptimedb_tpu.standalone import GreptimeDB

        ctx = TableContext(info.schema, {}, self.timezone)
        ts_range = extract_time_range(sel.where, ctx)
        stage = GreptimeDB(None)
        try:
            st_info = stage.catalog.create_table(
                stage.current_db, sel.table, info.schema, num_regions=1
            )
            region = stage.regions.create_region(
                st_info.region_ids[0], info.schema
            )
            for node, rids in by_node.items():
                table = self.datanodes[node].client.scan(
                    sel.table, rids, ts_range=ts_range
                )
                self.note_heartbeat(node)
                if table.num_rows == 0:
                    continue
                data = {}
                for name in table.column_names:
                    col = table.column(name)
                    if str(col.type) in ("string", "large_string"):
                        data[name] = np.asarray(col.to_pylist(), dtype=object)
                    else:
                        data[name] = col.to_numpy(zero_copy_only=False)
                region.write(data)
            return stage.sql(raw_sql)
        finally:
            stage.close()

    def _shape(self, sel: Select, res: QueryResult) -> QueryResult:
        """ORDER BY / LIMIT over merged partial results (frontend side of
        MergeScan: the non-commutative suffix)."""
        if sel.order_by:
            idx = {n: i for i, n in enumerate(res.column_names)}

            def sort_key(row):
                key = []
                for ob in sel.order_by:
                    name = str(ob.expr)
                    if name not in idx:
                        raise Unsupported(
                            f"distributed ORDER BY {name}: not an output "
                            "column"
                        )
                    key.append(SortVal(row[idx[name]], ob.asc))
                return key

            res.rows.sort(key=sort_key)
        if sel.limit is not None:
            res.rows[:] = res.rows[: sel.limit]
        return res


# ---------------------------------------------------------------------------
# Frontend role process: HTTP SQL over the distributed engine
# (reference src/cmd/src/frontend.rs — a stateless router binding the
# protocol surface to remote datanodes + a shared metadata store)
# ---------------------------------------------------------------------------


def _make_frontend_http(frontend: DistFrontend, host: str, port: int):
    """Frontend-role HTTP server on the shared ThreadedAiohttpApp
    machinery (one loop-hosting recipe for every aiohttp server):
    /v1/sql with the greptime JSON envelope, /health, /status. Query
    execution is the DistFrontend MergeScan path; the full protocol zoo
    stays on standalone (the reference's frontend serves more, but SQL
    is the spine every BI/driver integration needs)."""
    from greptimedb_tpu.servers.http import ThreadedAiohttpApp

    class FrontendHttp(ThreadedAiohttpApp):
        thread_name = "greptime-frontend-http"

        def __init__(self):
            self.frontend = frontend
            self.host = host
            self.port = port

        def build_app(self):
            import asyncio as _asyncio
            import time as _time

            from aiohttp import web

            from greptimedb_tpu.servers.http import (
                _error_json, _json_reply, _result_to_json,
            )

            async def h_sql(request):
                t0 = _time.perf_counter()
                sql = request.query.get("sql")
                if not sql and request.method == "POST":
                    form = await request.post()
                    sql = form.get("sql")
                if not sql:
                    return web.json_response(
                        {"code": 1004, "error": "missing sql parameter"},
                        status=400)
                try:
                    res = await _asyncio.get_running_loop().run_in_executor(
                        None, self.frontend.sql, sql)
                    return _json_reply(_result_to_json(res, t0), "/v1/sql")
                except Exception as e:  # noqa: BLE001
                    body, status = _error_json(e)
                    return web.json_response(body, status=status)

            async def h_health(request):
                return web.json_response({})

            async def h_status(request):
                return web.json_response({
                    "version": "greptimedb-tpu-0.1.0",
                    "role": "frontend",
                    "datanodes": {
                        str(nid): dn.address
                        for nid, dn in self.frontend.datanodes.items()
                    },
                    "tables": len(self.frontend.catalog.list_tables(
                        self.frontend.db)),
                })

            app = web.Application()
            app.router.add_route("*", "/v1/sql", h_sql)
            app.router.add_get("/health", h_health)
            app.router.add_get("/status", h_status)
            return app

    return FrontendHttp()


def serve_frontend(kvstore: str | None, datanodes: list[str],
                   host: str = "127.0.0.1", port: int = 4000) -> None:
    """Blocking entry point for the frontend role process
    (``greptime frontend start``)."""
    import json as _json

    kv = None
    if kvstore:
        from greptimedb_tpu.rpc.kvservice import RemoteKv

        kv = RemoteKv(kvstore[len("remote://"):]
                      if kvstore.startswith("remote://") else kvstore)
    fe = DistFrontend(kv=kv)
    for spec in datanodes:
        nid, addr = spec.split("=", 1)
        fe.add_datanode(int(nid), addr)
    srv = _make_frontend_http(fe, host=host, port=port)
    srv.start()
    print(_json.dumps({"role": "frontend",
                       "address": f"{srv.host}:{srv.port}"}), flush=True)
    import signal
    import threading

    stop = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_: stop.set())
    stop.wait()
    srv.stop()
    fe.close()
