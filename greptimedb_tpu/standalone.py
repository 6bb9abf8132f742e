"""Standalone database: all components wired in one process.

Equivalent of `greptime standalone start` composition
(src/cmd/src/standalone.rs:367 Instance::build_with): embedded kv metadata,
catalog, region engine, query engine and (later) protocol servers — no
process boundaries. This is also the StatementExecutor
(src/operator/src/statement.rs:211): every SQL statement dispatches here.
"""

from __future__ import annotations

import os

import numpy as np

from greptimedb_tpu.datatypes.schema import ColumnSchema, Schema
from greptimedb_tpu.datatypes.types import ConcreteDataType, SemanticType
from greptimedb_tpu.errors import (
    InvalidArguments, PlanError, TableAlreadyExists, TableNotFound,
    Unsupported,
)
from greptimedb_tpu.meta.catalog import DEFAULT_DB, CatalogManager, TableInfo
from greptimedb_tpu.meta.kv import FileKv, KvBackend, MemoryKv
from greptimedb_tpu.query.ast import (
    Admin, AlterTable, ColumnDef, CreateDatabase, CreateFlow, CreateTable,
    CreateView, Delete, DescribeTable, DropDatabase, DropFlow, DropTable,
    DropView, Explain, Insert, Select, ShowCreateTable, ShowDatabases,
    ShowFlows, ShowTables, Statement, Tql, TruncateTable, Use,
)
from greptimedb_tpu.query.engine import QueryEngine, QueryResult, TableProvider
from greptimedb_tpu.query.exprs import TableContext
from greptimedb_tpu.query.parser import parse_sql
from greptimedb_tpu.query.planner import SelectPlan
from greptimedb_tpu.storage.cache import RegionCacheManager
from greptimedb_tpu.storage.region import RegionEngine, RegionOptions
from greptimedb_tpu.utils.telemetry import REGISTRY

# Per-engine query latency (reference METRIC_HANDLE_SQL_ELAPSED /
# METRIC_HANDLE_PROMQL_ELAPSED in src/servers/src/metrics.rs): one
# histogram labelled by which engine evaluated the statement batch —
# "sql" (query/engine.py) or "promql" (TQL via promql/engine.py).  The
# per-protocol twin lives in the protocol servers
# (greptime_protocol_query_duration_seconds).
M_QUERY_DURATION = REGISTRY.histogram(
    "greptime_query_duration_seconds",
    "SQL/TQL statement-batch latency by evaluating engine",
    labels=("engine",),
)


def schema_from_create(stmt: "CreateTable") -> Schema:
    """CREATE TABLE statement → Schema (time index + tags + fields);
    shared by the standalone executor and the distributed frontend."""
    time_index = stmt.time_index
    cols: list[ColumnSchema] = []
    for cd in stmt.columns:
        dtype = ConcreteDataType.parse(cd.type_name)
        if cd.name == time_index:
            semantic = SemanticType.TIMESTAMP
            if not dtype.is_timestamp:
                raise InvalidArguments(
                    f"time index {cd.name} must be a timestamp, got {cd.type_name}"
                )
        elif cd.name in stmt.primary_keys:
            semantic = SemanticType.TAG
        else:
            semantic = SemanticType.FIELD
        cols.append(
            ColumnSchema(
                cd.name, dtype, semantic,
                nullable=cd.nullable and semantic is not SemanticType.TIMESTAMP,
                default=cd.default,
            )
        )
    schema = Schema(tuple(cols))
    if schema.time_index is None:
        raise InvalidArguments("missing TIME INDEX")
    return schema


def insert_rows_to_columns(
    stmt: "Insert", schema: Schema, timezone: str = "UTC"
) -> tuple[list[str], dict[str, list]]:
    """INSERT statement → validated column lists (timestamp strings
    localized to epoch ints); shared by the standalone executor and the
    distributed frontend."""
    columns = stmt.columns or [c.name for c in schema]
    if any(not schema.has_column(c) for c in columns):
        bad = [c for c in columns if not schema.has_column(c)]
        raise InvalidArguments(f"unknown insert columns {bad}")
    data: dict[str, list] = {c: [] for c in columns}
    for row in stmt.rows:
        if len(row) != len(columns):
            raise InvalidArguments(
                f"row has {len(row)} values, expected {len(columns)}"
            )
        for c, v in zip(columns, row):
            data[c].append(v)
    ts_name = schema.time_index.name
    if ts_name in data:
        ctx = TableContext(schema, {}, timezone)
        data[ts_name] = [ctx.ts_literal(v) for v in data[ts_name]]
    return columns, data


class CombinedRegionView:
    """Frontend-side merge view over a partitioned table's regions.

    The single-node analog of MergeScanExec (reference merge_scan.rs:210):
    partial scans from every region concatenate on host, tag codes are
    re-encoded into one table-wide dictionary space, and a global series id
    is assigned — after which the query engine sees one DeviceTable exactly
    as for an unpartitioned table. Duck-types the Region surface the cache
    and planners consume (schema/encoders/_series/num_series/generation/
    scan_host).
    """

    def __init__(self, table_key: str, regions: list):
        self.table_key = table_key
        self.regions = regions
        self.schema = regions[0].schema
        # strictly negative: disjoint from real region ids in the cache
        self.region_id = -(abs(hash(table_key)) % (1 << 40)) - 1
        self.encoders: dict[str, object] = {}
        self._series: dict[tuple, int] = {}
        self._built_for: tuple | None = None
        self._refresh()

    @property
    def generation(self) -> int:
        return sum(r.generation for r in self.regions) + len(self.regions)

    @property
    def series_generation(self) -> tuple:
        """Registry-only version (see Region.series_generation): the
        combined dictionaries/series rebuild deterministically from the
        member registries, so the tuple of member versions is
        content-stable across data-only appends."""
        return tuple(r.series_generation for r in self.regions)

    @property
    def tag_names(self) -> list[str]:
        return [c.name for c in self.schema.tag_columns]

    @property
    def num_series(self) -> int:
        self._refresh()
        return len(self._series)

    def ts_bounds(self) -> tuple[int, int] | None:
        bounds = [b for b in (r.ts_bounds() for r in self.regions)
                  if b is not None]
        if not bounds:
            return None
        return (min(b[0] for b in bounds), max(b[1] for b in bounds))

    def _refresh(self) -> None:
        """(Re)build combined dictionaries deterministically: region order,
        then each region's insertion order — stable for append-only dicts."""
        gen = tuple(r.generation for r in self.regions)
        if self._built_for == gen:
            return
        from greptimedb_tpu.datatypes.batch import DictionaryEncoder

        self.encoders = {name: DictionaryEncoder() for name in self.tag_names}
        self._series = {}
        for r in self.regions:
            code_maps = {}
            for name in self.tag_names:
                enc = self.encoders[name]
                code_maps[name] = [
                    enc.get_or_insert(v) for v in r.encoders[name].values()
                ]
            for key, _tsid in sorted(r._series.items(), key=lambda kv: kv[1]):
                gkey = tuple(
                    code_maps[name][code]
                    for name, code in zip(r.tag_names, key)
                )
                if gkey not in self._series:
                    self._series[gkey] = len(self._series)
        self._built_for = gen

    def scan_host(self, ts_range=(None, None), columns=None, tag_filters=None,
                  tag_preds=None, ft_tokens=None):
        import numpy as np

        from greptimedb_tpu.storage.memtable import SEQ, TSID
        from greptimedb_tpu.storage.region import Region

        self._refresh()
        parts = [r.scan_host(ts_range, columns, tag_filters, tag_preds,
                             ft_tokens)
                 for r in self.regions]
        names = list(parts[0].keys())
        merged = {k: np.concatenate([p[k] for p in parts]) for k in names}
        n = len(merged[SEQ])
        # recompute a table-global tsid from raw tag values
        merged[TSID] = Region._encode_tags(self, merged, n)
        ts_name = self.schema.time_index.name
        order = np.lexsort((merged[ts_name], merged[TSID]))
        return {k: v[order] for k, v in merged.items()}


class GreptimeDB(TableProvider):
    """The standalone instance: SQL in, results out."""

    def __init__(
        self,
        data_home: str | None = None,
        *,
        region_options: RegionOptions | None = None,
        cache_capacity_bytes: int = 8 << 30,
        metadata_store: str | None = None,
        plugins: list[str] | None = None,
        ingest_quota_bytes: int | None = None,
        ingest_quota_policy: str = "reject",
    ):
        """``metadata_store`` selects the kv backend (reference
        [metadata_store]/meta backend config): None → file-backed (or
        memory when data_home is None), "sqlite" → SqliteKv (RDS
        analog), "memory", or "remote://host:port" → shared KvServer
        (etcd analog).  ``plugins``: module paths loaded via
        utils/plugins.py (UDFs, processors, auth providers)."""
        # initialise the configured backend now: a platform that cannot
        # come up raises here, at open, and never serves from another one
        import jax as _jax

        devs = _jax.devices()

        self.memory_mode = data_home is None
        if data_home is None:
            import tempfile

            self._tmp = tempfile.TemporaryDirectory(prefix="greptimedb_tpu_")
            data_home = self._tmp.name
        self.data_home = data_home
        os.makedirs(data_home, exist_ok=True)
        if metadata_store is None:
            self.kv: KvBackend = (
                MemoryKv()
                if self.memory_mode
                else FileKv(os.path.join(data_home, "metadata", "kv.json"))
            )
        elif metadata_store == "memory":
            self.kv = MemoryKv()
        elif metadata_store == "sqlite":
            from greptimedb_tpu.meta.kv import SqliteKv

            self.kv = SqliteKv(
                os.path.join(data_home, "metadata", "kv.sqlite"))
        elif metadata_store.startswith("remote://"):
            from greptimedb_tpu.rpc.kvservice import RemoteKv

            self.kv = RemoteKv(metadata_store[len("remote://"):])
        else:
            raise InvalidArguments(
                f"unknown metadata_store {metadata_store!r}")
        self.catalog = CatalogManager(self.kv)
        self.regions = RegionEngine(
            os.path.join(data_home, "data"), region_options
        )
        # multi-device: form the series-axis mesh so resident grids shard
        # across chips and the aggregate kernels run SPMD with XLA-
        # inserted collectives (reference MergeScanExec fan-out/merge,
        # src/query/src/dist_plan/merge_scan.rs:210 — here the exchange
        # is GSPMD over ICI, not a Flight shuffle). GREPTIME_MESH=off
        # forces single-device execution for A/B comparison.
        self.mesh = None
        if (len(devs) > 1
                and os.environ.get("GREPTIME_MESH", "auto") != "off"):
            from jax.sharding import Mesh as _Mesh

            self.mesh = _Mesh(
                np.array(devs), (os.environ.get("GREPTIME_MESH_AXIS",
                                                "shard"),)
            )
        self.cache = RegionCacheManager(cache_capacity_bytes,
                                        mesh=self.mesh)
        # workload memory quotas (reference common-memory-manager): the
        # ingest write-buffer quota reclaims by flushing the largest
        # memtable before rejecting; the device cache registers for
        # observability (its LRU already enforces capacity_bytes)
        from greptimedb_tpu.utils.memory import WorkloadMemoryManager

        self.memory = WorkloadMemoryManager()
        self.memory.register(
            "ingest", ingest_quota_bytes,
            # list() snapshots the dict (atomic under the GIL): usage is
            # read from the event loop (/status) while executor threads
            # add regions via CREATE TABLE
            usage_fn=lambda: sum(
                r.memtable.bytes
                for r in list(self.regions.regions.values())
            ),
            reclaim_fn=self._flush_largest_memtable,
            policy=ingest_quota_policy,
        )
        self.memory.register(
            "device_cache", None, usage_fn=lambda: self.cache._bytes,
        )
        self.regions.memory = self.memory
        self.engine = QueryEngine(self)
        # derived bucket-major layout cache (aligned-window range path):
        # the extra resident copy admits against its own workload quota
        # with reject-to-fallback — an over-budget build degrades to the
        # dynamic-slice kernel instead of OOMing HBM; admission pressure
        # reclaims by LRU eviction
        _layout = self.engine.executor.layout_cache
        _layout_quota = os.environ.get("GREPTIME_LAYOUT_CACHE_QUOTA_BYTES")
        self.memory.register(
            "layout_cache",
            int(_layout_quota) if _layout_quota else None,
            usage_fn=lambda: self.engine.executor.layout_cache.bytes,
            reclaim_fn=_layout.reclaim,
            policy="reject",
        )
        _layout.memory_probe = (
            lambda n: self.memory.try_admit("layout_cache", n)
        )
        # chain drop/truncate/repartition invalidation into the derived
        # layouts so a dead region's partials free immediately
        self.cache.derived_layouts = _layout
        # resident PromQL evaluation cache (promql/engine.py): matched
        # tsid selections, composite-key sort layouts and group-id
        # vectors, generation-invalidated like the SQL layout cache and
        # admitted under its own workload quota with reject-to-fallback
        from greptimedb_tpu.storage.cache import PromLayoutCache

        self.promql_cache = PromLayoutCache(mesh=self.mesh)
        _pq_quota = os.environ.get("GREPTIME_PROMQL_CACHE_QUOTA_BYTES")
        self.memory.register(
            "promql_cache",
            int(_pq_quota) if _pq_quota else None,
            usage_fn=lambda: self.promql_cache.bytes,
            reclaim_fn=self.promql_cache.reclaim,
            policy="reject",
        )
        self.promql_cache.memory_probe = (
            lambda n: self.memory.try_admit("promql_cache", n)
        )
        self.cache.promql_derived = self.promql_cache
        # resident fulltext fingerprint index (fulltext/resident.py):
        # matrices + verified-vocabulary memos admit under their own
        # workload quota with reject-to-fallback — an over-budget build
        # degrades to the host predicate loop instead of OOMing HBM
        _ft = self.engine.executor.fulltext_cache
        _ft_quota = os.environ.get("GREPTIME_FULLTEXT_QUOTA_BYTES")
        self.memory.register(
            "fulltext",
            int(_ft_quota) if _ft_quota else None,
            usage_fn=lambda: _ft.bytes,
            reclaim_fn=_ft.reclaim,
            policy="reject",
        )
        _ft.memory_probe = (
            lambda n: self.memory.try_admit("fulltext", n)
        )
        # cold-scan staging buffers (storage/scan.py): the parallel SST
        # decode pool admits its estimated in-flight decode bytes with
        # reject-to-SEQUENTIAL fallback — over quota, a scan degrades to
        # the one-file-at-a-time loop instead of failing the query
        from greptimedb_tpu.storage import scan as _scanmod

        _scan_quota = os.environ.get("GREPTIME_SCAN_QUOTA_BYTES")
        self.memory.register(
            "scan",
            int(_scan_quota) if _scan_quota else None,
            usage_fn=_scanmod.staging_bytes,
            policy="reject",
        )
        # query-compiler subsystem (compile/): persistent AOT store +
        # shape-class usage journal.  "auto" arms it for persistent data
        # homes; memory-mode (ephemeral test) instances stay memory-only
        # unless explicitly forced on.  Explicit "on" ALSO places jax's
        # own compilation cache (compile/xla_cache.py) so jits outside
        # the routed kernel sites persist their XLA artifacts too.
        self.plan_compiler = self.engine.executor.compiler
        _cc_mode = os.environ.get("GREPTIME_COMPILE_CACHE", "auto").lower()
        _cc_forced = _cc_mode in ("on", "1", "true")
        self._compile_cache_enabled = _cc_mode not in (
            "off", "0", "false") and (_cc_forced or not self.memory_mode)
        if self._compile_cache_enabled:
            _cc_dir = os.environ.get("GREPTIME_COMPILE_CACHE_DIR") or (
                os.path.join(data_home, "compile_cache"))
            _cc_quota = os.environ.get("GREPTIME_COMPILE_CACHE_QUOTA_BYTES")
            _cc_quota = int(_cc_quota) if _cc_quota else None
            try:
                self.plan_compiler.configure(_cc_dir, _cc_quota)
            except OSError:
                self._compile_cache_enabled = False  # unwritable dir
            else:
                _store = self.plan_compiler.store
                self.memory.register(
                    "compile_cache", _cc_quota,
                    # disk, not HBM: serialized executables on local disk
                    usage_fn=_store.bytes,
                    reclaim_fn=_store.reclaim,
                    policy="best_effort",
                    kind="disk",
                )
                if _cc_forced:
                    from greptimedb_tpu.compile.xla_cache import (
                        configure_xla_cache,
                    )

                    configure_xla_cache()
        # nested (sub)queries route through the full statement dispatch so
        # information_schema / pg_catalog subqueries resolve
        self.engine.dispatch = self.execute_statement
        self.current_db = DEFAULT_DB
        self._views: dict[str, CombinedRegionView] = {}
        # the storage engine is single-writer (region sequence assignment and
        # memtable mutation are unsynchronized, like mito2's per-region
        # worker loop); with three protocol servers calling in, correctness
        # comes from this lock, not from any particular executor topology
        import threading as _threading

        self._lock = _threading.RLock()
        # before the flow engine: restoring a flow at registration plans
        # its query (table_context reads the session timezone) and asks
        # the metric engine whether a source table is logical
        self.timezone = "UTC"  # SET time_zone / config default_timezone
        from greptimedb_tpu.storage.metric_engine import MetricEngine

        self.metric_engine = MetricEngine(self)
        # device flow runtime (flow/device.py): resident [G, W] partial
        # state, one-dispatch ingest folds, GTF1 checkpoints with exact
        # WAL watermarks (flow/checkpoint.py).  GREPTIME_FLOW_DEVICE=off
        # keeps the host dict-of-partials engine byte-for-byte — the
        # modules are then never imported.
        self.flow_runtime = None
        self.flow_checkpoints = None
        if os.environ.get("GREPTIME_FLOW_DEVICE", "on").lower() not in (
                "off", "0", "false"):
            from greptimedb_tpu.flow.checkpoint import FlowCheckpointStore
            from greptimedb_tpu.flow.device import FlowDeviceRuntime

            self.flow_runtime = FlowDeviceRuntime(self)
            try:
                self.flow_checkpoints = FlowCheckpointStore(
                    os.path.join(data_home, "flow_ckpt"))
            except OSError:
                self.flow_checkpoints = None  # unwritable home
            _flow_quota = os.environ.get("GREPTIME_FLOW_QUOTA_BYTES")
            self.memory.register(
                "flow",
                int(_flow_quota) if _flow_quota else None,
                usage_fn=self.flow_runtime.nbytes,
                policy="reject",
            )
            self.flow_runtime.memory_probe = (
                lambda n: self.memory.try_admit("flow", n)
            )
        from greptimedb_tpu.flow.engine import FlowEngine

        self.flow_engine = FlowEngine(self)
        from greptimedb_tpu.utils.auth import StaticUserProvider

        self.user_provider = StaticUserProvider()
        self.plugins = None
        if plugins:
            from greptimedb_tpu.utils.plugins import load_plugins

            self.plugins = load_plugins(plugins, db=self)
        # slow-query recorder (reference common-event-recorder + the
        # greptime_private.slow_queries system table): queries slower than
        # the threshold are appended to a private table; 0 disables
        self.slow_query_threshold_ms: float = 0.0
        self._recording_slow_query = False
        # live query registry (reference src/catalog/src/process_manager.rs):
        # SHOW PROCESSLIST / information_schema.process_list / KILL <id>
        from greptimedb_tpu.meta.process import ProcessManager

        self.processes = ProcessManager()
        self._proc_local = _threading.local()
        # concurrent serving layer (serving/): protocol servers submit
        # queries through the scheduler — per-tenant admission, priority
        # classes, deadline shedding, cross-query stacked dispatch.
        # Worker threads start lazily on the first submit, so an
        # embedded db.sql() pays only this attribute.
        from greptimedb_tpu.serving import QueryScheduler

        self.scheduler = QueryScheduler(self)
        # closed-loop SLO observatory (ISSUE 18, serving/slo.py +
        # serving/idle.py), owned by the scheduler: per-(tenant, class,
        # protocol) latency sketches, error budgets and burn-rate
        # alerts, plus the budgeted idle economy that arbitrates the
        # scheduler's idle capacity between warmup / flow checkpoints /
        # scrubbing / journal drains.
        self.slo = self.scheduler.slo
        self.idle_economy = self.scheduler.idle_economy
        # persistent procedure manager (repartition etc.): one instance so
        # table locks are process-wide; RUNNING journals from a crashed
        # process resume here at startup
        from greptimedb_tpu.meta.ddl import (
            AlterOptionsProcedure, AlterTableProcedure, CreateTableProcedure,
            DropTableProcedure,
        )
        from greptimedb_tpu.meta.procedure import ProcedureManager
        from greptimedb_tpu.meta.repartition import RepartitionProcedure

        self.procedures = ProcedureManager(self.kv, services={"db": self})
        self.procedures.register(RepartitionProcedure)
        self.procedures.register(CreateTableProcedure)
        self.procedures.register(DropTableProcedure)
        self.procedures.register(AlterTableProcedure)
        self.procedures.register(AlterOptionsProcedure)
        try:
            resumed = self.procedures.recover()
            if resumed:
                import sys as _sys

                print(f"resumed {len(resumed)} interrupted procedure(s)",
                      file=_sys.stderr)
        except Exception as e:  # noqa: BLE001 (startup must not die on a
            # poisoned procedure; it stays journaled for inspection)
            import sys as _sys

            print(f"procedure recovery failed: {e}", file=_sys.stderr)
        # self-monitoring loop (reference export_metrics self_import +
        # self trace export): a timer writes the Tracer span buffer into
        # opentelemetry_traces and snapshots the metrics registry into
        # internal tables, both through the normal ingest path.  OFF by
        # default — the knob also gates the import, so a disabled
        # instance never loads the exporter module and the query hot
        # path carries zero extra allocations.
        self.self_monitor = None
        if os.environ.get("GREPTIME_SELF_MONITOR", "").lower() in (
                "1", "true", "on"):
            from greptimedb_tpu.utils.selfmonitor import SelfMonitor

            self.self_monitor = SelfMonitor(
                self, interval_s=float(os.environ.get(
                    "GREPTIME_SELF_MONITOR_INTERVAL_S", "30")))
            self.self_monitor.start()
        # AOT warmup (compile/warmup.py): every local region is open by
        # now, so replay the usage journal's top-K shape classes — a
        # restarted node serves its hot query classes with kernels (and
        # the resident grids the replays build) already warm; with a
        # populated AOT store the replays deserialize instead of
        # compiling.  Remaining classes drain through the scheduler's
        # idle hook, one statement per idle tick.
        self.warmup = None
        _wm = os.environ.get("GREPTIME_AOT_WARMUP", "auto").lower()
        if (self._compile_cache_enabled
                and _wm not in ("off", "0", "false")
                and self.plan_compiler.journal is not None
                and len(self.plan_compiler.journal)):
            from greptimedb_tpu.compile.warmup import WarmupService

            self.warmup = WarmupService(
                self, self.plan_compiler,
                top_k=int(os.environ.get("GREPTIME_AOT_WARMUP_TOP_K", "8")))
            self.warmup.warm_on_open()
            if self.warmup.pending():
                # kick (the default) wakes/starts the workers: an idle
                # standby node must drain its warmup queue without
                # waiting for traffic
                self.scheduler.add_idle_hook(self.warmup.idle_tick)
        # online integrity scrubber (storage/scrubber.py, ISSUE 15): a
        # low-priority verified sweep over cold SSTs / manifest files /
        # WAL segments / grid snapshots / the S3 read cache on the
        # scheduler's idle capacity, preempted by interactive queries.
        # `auto` (default) arms it for persistent data homes but lets
        # the worker pool start lazily with the first served query;
        # `on` starts sweeping immediately (a standby node scrubs too).
        self.scrubber = None
        _sc = os.environ.get("GREPTIME_SCRUB", "auto").lower()
        if _sc not in ("off", "0", "false") and not self.memory_mode:
            from greptimedb_tpu.storage.scrubber import Scrubber

            self.scrubber = Scrubber(
                self.regions,
                snapshot_dirs=[os.path.join(data_home, "grid_snap")])
            self.scheduler.add_idle_hook(
                self.scrubber.tick, kick=_sc in ("on", "1", "true"))
        # journal/cache drain as a WEIGHTED idle consumer: usage-journal
        # persistence stops riding the note() call's save-every-8
        # hiccup exclusively and instead drains on granted idle ticks
        # like every other background consumer (cheap, so low weight)
        if getattr(self.plan_compiler, "journal", None) is not None:
            self.scheduler.add_idle_hook(
                self._journal_drain_tick, kick=False,
                name="journal_drain", weight=0.5)

    def _journal_drain_tick(self) -> bool:
        """Idle-economy consumer: persist the usage journal when it has
        unsaved notes; drained (False) once clean."""
        j = getattr(self.plan_compiler, "journal", None)
        if j is None:
            return False
        if getattr(j, "_dirty", 0) > 0:
            j.save()
            return True
        return False

    def _flush_largest_memtable(self, needed_bytes: int) -> None:
        """Ingest-quota reclaimer: flush memtables largest-first until the
        needed headroom exists (mito's write-buffer-full flush trigger)."""
        regions = sorted(
            list(self.regions.regions.values()),
            key=lambda r: r.memtable.bytes, reverse=True,
        )
        freed = 0
        for r in regions:
            if freed >= needed_bytes:
                break
            b = r.memtable.bytes
            if b == 0:
                break
            r.flush()
            freed += b

    def close(self, flush: bool = False) -> None:
        """Shut the instance down: drain the scheduler, stop the
        self-monitor, close region WAL handles, close the kv store.
        ``flush=True`` (the graceful SIGTERM server path) also flushes
        dirty regions so a clean restart replays O(hot-tail)."""
        # unhook idle warmup first: a tick claimed after this point
        # would replay statements against a closing instance
        self.scheduler.idle_hook = None
        self.scheduler.stop()
        if self.flow_checkpoints is not None:
            # final checkpoints: a clean restart resumes every flow from
            # its exact watermark with zero tail to replay
            try:
                self.flow_engine.checkpoint_now()
            except Exception:  # noqa: BLE001 — shutdown must not die on
                pass  # a checkpoint failure; restart reseeds instead
        if self.self_monitor is not None:
            self.self_monitor.stop()
        # persist the shape-class usage journal so the next boot warms
        # what this session actually ran
        self.plan_compiler.close()
        self.regions.close(flush=flush)
        if hasattr(self.kv, "close"):
            self.kv.close()

    # ---- TableProvider -------------------------------------------------
    def _split_name(self, table: str) -> tuple[str, str]:
        if "." in table:
            db, name = table.rsplit(".", 1)
            return db, name
        return self.current_db, table

    def _open_or_create(self, region_id: int, schema):
        try:
            return self.regions.open_region(region_id)
        except Exception:
            return self.regions.create_region(region_id, schema)

    def _regions_of(self, table: str) -> list:
        db, name = self._split_name(table)
        info = self.catalog.get_table(db, name)
        return [self._open_or_create(rid, info.schema) for rid in info.region_ids]

    def _region_of(self, table: str):
        return self._regions_of(table)[0]

    def _table_view(self, table: str):
        """Region, partitioned merge view, metric-engine logical view, or
        read-only external file view (file engine)."""
        db, name = self._split_name(table)
        if self.metric_engine.is_logical(db, name):
            return self.metric_engine.view(db, name)
        info = None
        try:
            info = self.catalog.get_table(db, name)
        except TableNotFound:
            pass
        if info is not None and info.engine == "file":
            from greptimedb_tpu.storage.file_engine import FileTableView

            cache = getattr(self, "_file_views", None)
            if cache is None:
                cache = self._file_views = {}
            v = cache.get((db, name))
            if v is None:
                v = FileTableView(
                    name, info.schema, info.options["location"],
                    info.options.get("format", "parquet"), info.table_id,
                )
                cache[(db, name)] = v
            return v
        regions = self._regions_of(table)
        if len(regions) == 1:
            return regions[0]
        db, name = self._split_name(table)
        key = f"{db}.{name}"
        view = self._views.get(key)
        if view is None or not (
            len(view.regions) == len(regions)
            and all(a is b for a, b in zip(view.regions, regions))
        ):
            # nonce: a rebuilt view (repartition swapped the region set)
            # must not share the old view's device-cache identity — fresh
            # regions restart at low generations that could collide with
            # cached entries
            self._view_nonce = getattr(self, "_view_nonce", 0) + 1
            view = CombinedRegionView(f"{key}#{self._view_nonce}", regions)
            self._views[key] = view
        view._refresh()  # planning needs current combined dictionaries
        return view

    def _partition_rule(self, table: str):
        from greptimedb_tpu.parallel.partition import PartitionRule

        db, name = self._split_name(table)
        info = self.catalog.get_table(db, name)
        if info.partition_exprs:
            return PartitionRule.from_sql(info.partition_columns,
                                          info.partition_exprs)
        return PartitionRule.hash_rule(
            len(info.region_ids),
            [c.name for c in info.schema.tag_columns],
        )

    def table_context(self, table: str) -> TableContext:
        view = self._table_view(table)
        return TableContext(view.schema, view.encoders, self.timezone)

    def device_table(self, table: str, plan: SelectPlan):
        view = self._table_view(table)
        dt = self.cache.get(view)
        return dt, view.ts_bounds() or (0, 0)

    def grid_table(self, table: str, plan: SelectPlan):
        """Dense time-grid resident table (storage/grid.py) for eligible
        single-region tables; (None, bounds) otherwise — the engine falls
        back to the row-oriented DeviceTable path."""
        view = self._table_view(table)
        gt = self.cache.get_grid(view)
        return gt, view.ts_bounds() or (0, 0)

    def mesh_select(self, sel):
        """Mesh row path for tables the dense grid refuses (irregular /
        sparse cadence): shard rows on the series axis across the device
        mesh and aggregate with ICI collectives through the SAME
        commutativity split as the Flight exchange (reference
        src/query/src/dist_plan/merge_scan.rs:210,335 fans out any
        pushable plan; here the fan-out is shard_map over a resident
        ShardedTable).  Returns (names, rows) unordered, or None when the
        query is not mesh-decomposable — the engine falls back to the
        single-device row path."""
        if self.mesh is None:
            return None
        view = self._table_view(sel.table)
        if getattr(view, "base_version", None) is None:
            return None  # duck-typed views (joins, staged scans, system)
        # fan-out pays only at scale: below the threshold one device wins
        # (shard_map compile + collective latency vs a single fused kernel)
        min_rows = int(os.environ.get("GREPTIME_MESH_MIN_ROWS", "65536"))
        memtable = getattr(view, "memtable", None)
        if memtable is None:
            return None  # e.g. FileTableView: no LSM parts to shard
        live = memtable.num_rows + sum(
            m.num_rows for m in view.sst_files)
        if live < min_rows:
            return None
        from greptimedb_tpu.rpc.partial import split_partial

        ts_name = (view.schema.time_index.name
                   if view.schema.time_index is not None else None)
        if split_partial(sel, ts_column=ts_name) is None:
            return None  # cheap pre-check before building the shard table
        from greptimedb_tpu.parallel.dist import (
            DistAggExecutor, execute_select_on_mesh,
        )

        st = self.cache.get_sharded(view)
        if st is None:
            return None
        if getattr(self, "_dist_exec", None) is None:
            self._dist_exec = DistAggExecutor(self.mesh)
        return execute_select_on_mesh(
            self._dist_exec, st, sel, self.table_context(sel.table),
            view.ts_bounds())

    def host_columns(self, table: str, ts_range=(None, None)) -> dict:
        """Raw host scan for operators that run host-side (join matching)."""
        return self._table_view(table).scan_host(ts_range)

    # ---- SQL entry -----------------------------------------------------
    def sql(self, query: str, client: str = "",
            _stmts: list | None = None) -> QueryResult:
        """Execute one or more statements; returns the LAST result.
        ``_stmts`` carries pre-parsed statements from sql_in_db so the
        wire path parses exactly once."""
        import time as _time

        from greptimedb_tpu.utils.tracing import TRACER

        # register BEFORE taking the executor lock so statements queued
        # behind a long query show up in (and are killable from) other
        # connections' SHOW PROCESSLIST; nested sql() calls (flows,
        # recorders, sql_in_db) reuse the outer ticket
        ticket = None
        if getattr(self._proc_local, "ticket", None) is None:
            # self.current_db is read lock-free here; a concurrent wire
            # session's temporary swap (sql_in_db) can mislabel the
            # ticket's schema column — display-only, accepted to keep
            # registration ahead of the lock wait
            ticket = self.processes.register(query, self.current_db, client)
            self._proc_local.ticket = ticket
        try:
            if _stmts is not None:
                stmts = _stmts
            else:
                with TRACER.stage("parse"):
                    stmts = parse_sql(query)
            fast = self._registry_only(stmts)
            if fast is not None:
                return fast
            return self._sql_locked(stmts, query, _time, TRACER)
        finally:
            if ticket is not None:
                self._proc_local.ticket = None
                self.processes.deregister(ticket)

    def _registry_only(self, stmts) -> QueryResult | None:
        """Execute KILL / SHOW PROCESSLIST scripts without the executor
        lock (they touch only the process registry, which has its own) —
        else a KILL would queue behind the very statement it is trying to
        cancel. Returns None if any statement needs the real executor."""
        from greptimedb_tpu.query.ast import Kill, ShowProcesslist

        if not stmts or not all(
            isinstance(s, (Kill, ShowProcesslist)) for s in stmts
        ):
            return None
        result = QueryResult([], [])
        for stmt in stmts:
            result = self.execute_statement(stmt)
        return result

    def try_fast_sql(self, query: str) -> QueryResult | None:
        """Protocol-server entry for registry-only statements: execute
        KILL / SHOW PROCESSLIST without the db executor pool or lock (so
        they cannot queue behind the statement they target), returning
        None for anything else — including unparsable input, which the
        normal path re-parses to raise its usual error.

        A cheap prefix test gates the real parse: this runs synchronously
        on the server event loop, and a multi-MB INSERT must not pay (or
        stall other connections on) a full tokenize here. Leading SQL
        comments are skipped so '/* retry */ KILL 7' still takes the
        fast path (the parser strips them anyway)."""
        head = query[:4096].lstrip()
        while True:
            if head.startswith("--"):
                _, _, head = head.partition("\n")
                head = head.lstrip()
            elif head.startswith("/*"):
                _, sep, head = head.partition("*/")
                if not sep:
                    return None  # unterminated comment: let the parser err
                head = head.lstrip()
            else:
                break
        head = head[:32].upper()
        if not (head.startswith("KILL") or
                (head.startswith("SHOW") and "PROCESS" in head)):
            return None
        try:
            stmts = parse_sql(query)
        except Exception:  # noqa: BLE001
            return None
        return self._registry_only(stmts)

    def check_cancelled(self) -> None:
        """Stage-boundary hook: raise Cancelled if this thread's current
        statement was KILLed from another connection."""
        t = getattr(self._proc_local, "ticket", None)
        if t is not None:
            t.check()

    def _sql_locked(self, stmts, query: str, _time, TRACER) -> QueryResult:
        with self._lock:
            t0 = _time.perf_counter()
            # per-statement stage sink: engines write their stage/device
            # timings here (query/engine.py mark(), promql stage_ms) so a
            # slow query self-reports where its time went.  Activated only
            # when the recorder will read it: a sink makes the engines
            # wait for the device after each dispatch, which the tracer
            # must not cause (it observes; the stages carry its times).
            sink: dict | None = None
            outer_sink = getattr(self._proc_local, "stage_sink", None)
            if outer_sink is None and self.slow_query_threshold_ms > 0:
                sink = {}
                # scheduler columns: a worker thread stamps its queue
                # wait/batch info before calling in, so slow_queries and
                # the trace both carry where the statement QUEUED, not
                # just where it ran
                sched = getattr(self._proc_local, "sched_info", None)
                if sched:
                    sink.update(sched)
                self._proc_local.stage_sink = sink
            engine = "promql" if any(
                isinstance(s, Tql) for s in stmts) else "sql"
            try:
                with TRACER.stage("sql", statement=query[:256]):
                    if not stmts:
                        return QueryResult([], [])
                    result = QueryResult([], [])
                    for stmt in stmts:
                        self.check_cancelled()
                        with TRACER.stage("execute_statement",
                                          kind=type(stmt).__name__):
                            result = self.execute_statement(stmt)
            finally:
                if sink is not None:
                    self._proc_local.stage_sink = None
                # statement boundary: kernel classes built OUTSIDE a
                # statement (batch paths, background work on this
                # thread) must journal replay-less, never this
                # statement's replay
                self.plan_compiler.clear_replay()
                elapsed_ms = (_time.perf_counter() - t0) * 1000
                M_QUERY_DURATION.labels(engine).observe(elapsed_ms / 1000)
            if (
                self.slow_query_threshold_ms > 0
                and elapsed_ms >= self.slow_query_threshold_ms
                and not self._recording_slow_query
                and any(isinstance(s, (Select, Tql)) for s in stmts)
            ):
                self._record_slow_query(query, elapsed_ms, stages=sink)
            return result

    @property
    def stage_sink(self) -> dict | None:
        """The active per-statement stage-timing sink for this thread (see
        _sql_locked), read by QueryEngine.execute_select and the PromQL
        evaluator; None when nothing is collecting."""
        return getattr(self._proc_local, "stage_sink", None)

    def _record_slow_query(self, query: str, elapsed_ms: float,
                           stages: dict | None = None) -> None:
        """Append to greptime_private.slow_queries (reference recorder.rs).
        ``stages`` is the statement's stage-timing sink (plan/device/shape
        ms, jit-cache state, PromQL stage breakdown) serialized as JSON so
        a slow query self-reports where its time went."""
        import json as _json
        import time as _time

        self._recording_slow_query = True  # the recorder must never recurse
        try:
            db = "greptime_private"
            self.catalog.create_database(db, if_not_exists=True)
            if not self.catalog.table_exists(db, "slow_queries"):
                schema = Schema((
                    ColumnSchema("ts", ConcreteDataType.TIMESTAMP_MILLISECOND,
                                 SemanticType.TIMESTAMP, nullable=False),
                    ColumnSchema("cost_ms", ConcreteDataType.FLOAT64),
                    ColumnSchema("threshold_ms", ConcreteDataType.FLOAT64),
                    ColumnSchema("query", ConcreteDataType.STRING),
                    ColumnSchema("stages", ConcreteDataType.STRING),
                    ColumnSchema("trace_id", ConcreteDataType.STRING),
                    # scheduler columns: queue wait and coalesced batch
                    # size when the statement came through serving/
                    ColumnSchema("sched_wait_ms", ConcreteDataType.FLOAT64),
                    ColumnSchema("sched_batch", ConcreteDataType.FLOAT64),
                ))
                info = self.catalog.create_table(db, "slow_queries", schema,
                                                 if_not_exists=True)
                if info is not None:
                    self.regions.create_region(info.region_ids[0], schema)
            region = self._region_of(f"{db}.slow_queries")
            row = {
                "ts": [int(_time.time() * 1000)],
                "cost_ms": [round(elapsed_ms, 3)],
                "threshold_ms": [self.slow_query_threshold_ms],
                "query": [query[:4096]],
            }
            if region.schema.has_column("stages"):
                # pre-existing data dirs may carry the older 4-column
                # schema; never fail the write over the extra column.
                # The column must stay VALID JSON: an oversized breakdown
                # drops its nested values (cache-event dicts etc.) rather
                # than byte-truncating mid-token
                text = ""
                if stages:
                    text = _json.dumps(stages, default=str)
                    if len(text) > 4096:
                        text = _json.dumps({
                            k: v for k, v in stages.items()
                            if isinstance(v, (int, float, str, bool))
                        }, default=str)
                    if len(text) > 4096:  # still huge: keep JSON valid
                        text = "{}"
                row["stages"] = [text]
            sched = getattr(self._proc_local, "sched_info", None) or {}
            if not sched and stages:
                sched = stages  # batch path: sink already carries them
            if region.schema.has_column("sched_wait_ms"):
                row["sched_wait_ms"] = [
                    float(sched.get("sched_wait_ms", 0.0))]
            if region.schema.has_column("sched_batch"):
                row["sched_batch"] = [float(sched.get("sched_batch", 0.0))]
            if region.schema.has_column("trace_id"):
                # the trace id the protocol layer returned to the client
                # (W3C traceparent / x-greptime-trace-id) — lets an
                # operator join a client-reported trace to its slow-query
                # record; "" when the statement carried no context
                from greptimedb_tpu.utils.tracing import TRACER

                row["trace_id"] = [TRACER.current_trace_id()]
            region.write(row)
        except Exception:  # noqa: BLE001 (recording must never fail queries)
            pass
        finally:
            self._recording_slow_query = False

    def set_timezone(self, tz: str) -> None:
        """Validate + apply the instance default timezone."""
        from greptimedb_tpu.errors import SyntaxError_
        from greptimedb_tpu.query.parser import resolve_timezone

        try:
            resolve_timezone(tz)
        except SyntaxError_ as e:
            raise InvalidArguments(str(e)) from None
        self.timezone = tz

    def sql_in_db(
        self, query: str, dbname: str, timezone: str | None = None,
        _stmts: list | None = None,
    ) -> tuple[QueryResult, str, str]:
        """Session-scoped execution for wire-protocol connections: run with
        the connection's database and timezone without leaking either to
        other connections. Returns (result, session db, session tz) —
        USE / SET time_zone move them.  ``_stmts`` hands over an already
        parsed statement list (the scheduler parses at submit for
        classification/batching) so the wire hot path parses once."""
        # register the ticket BEFORE blocking on the executor lock so a
        # wire statement queued behind a long query is visible in (and
        # killable from) SHOW PROCESSLIST; KILL / SHOW PROCESSLIST
        # short-circuit without the lock entirely
        stmts = _stmts
        if stmts is None:
            try:
                stmts = parse_sql(query)
            except Exception:  # noqa: BLE001 — normal path reports error
                stmts = None
        ticket = None
        if getattr(self._proc_local, "ticket", None) is None:
            ticket = self.processes.register(query, dbname)
            self._proc_local.ticket = ticket
        try:
            if stmts is not None:
                fast = self._registry_only(stmts)
                if fast is not None:
                    return fast, dbname, timezone or self.timezone
            with self._lock:
                prev_db = self.current_db
                prev_tz = self.timezone
                self.current_db = dbname
                if timezone is not None:
                    self.timezone = timezone
                try:
                    result = self.sql(query, _stmts=stmts)
                    return result, self.current_db, self.timezone
                finally:
                    self.current_db = prev_db
                    self.timezone = prev_tz
        finally:
            if ticket is not None:
                self._proc_local.ticket = None
                self.processes.deregister(ticket)

    def sql_batch(self, entries) -> list[QueryResult] | None:
        """Scheduler entry for one stacked dispatch over N coalesced
        Selects: ``entries`` is [(query_text, Select, dbname|None,
        timezone|None)].  Returns per-entry results (order preserved,
        bit-exact vs solo) or None when any member falls outside the
        batchable surface — the scheduler then executes each solo.
        Statement-level dispatch guards mirror execute_statement's Select
        branch exactly: system tables, views and derived tables never
        batch."""
        import time as _time

        from greptimedb_tpu.meta import information_schema as info
        from greptimedb_tpu.utils.tracing import TRACER  # noqa: F401

        sels = [s for _q, s, _d, _tz in entries]
        for s in sels:
            if (s.table is None or s.from_subquery is not None or s.joins
                    or info.is_information_schema(s.table)
                    or info.is_pg_catalog(s.table)
                    or s.table.lower() == "greptime_private.recycle_bin"):
                return None
            try:
                vdb, vname = self._split_name(s.table)
                if self.catalog.get_engine(vdb, vname) == "view":
                    return None
            except Exception:  # noqa: BLE001 — solo path owns the error
                return None
        with self._lock:
            # session entries were classified against current_db and the
            # instance timezone OUTSIDE the lock; a concurrent USE / SET
            # TIME ZONE could have moved either — re-verify under the
            # lock or fall back to solo session execution (which swaps
            # the session db/tz per statement)
            for _q, _s, dbname, tz in entries:
                if dbname is not None and dbname != self.current_db:
                    return None
                if tz is not None and tz != self.timezone:
                    return None
            t0 = _time.perf_counter()
            sink: dict = {}
            sched = getattr(self._proc_local, "sched_info", None)
            if sched:
                sink.update(sched)
            results = self.engine.execute_select_batch(sels, metrics=sink)
            elapsed_ms = (_time.perf_counter() - t0) * 1000
        if results is None:
            return None
        for (query, _s, _d, _tz), _res in zip(entries, results):
            # each member waited for the whole dispatch: observe the
            # batch wall per member, exactly what its client experienced
            M_QUERY_DURATION.labels("sql").observe(elapsed_ms / 1000)
            if (
                self.slow_query_threshold_ms > 0
                and elapsed_ms >= self.slow_query_threshold_ms
                and not self._recording_slow_query
            ):
                self._record_slow_query(query, elapsed_ms, stages=sink)
        return results

    def execute_statement(self, stmt: Statement) -> QueryResult:
        from greptimedb_tpu.query.ast import Union as UnionStmt

        if isinstance(stmt, UnionStmt):
            return self.engine.execute_union(stmt, self.execute_statement)
        if isinstance(stmt, Select):
            from greptimedb_tpu.meta import information_schema as info

            if info.is_information_schema(stmt.table):
                return info.execute(self, stmt)
            if stmt.table and stmt.table.lower() == \
                    "greptime_private.recycle_bin":
                # reference location of the soft-drop listing
                # (purge_dropped_table.rs); same builder as
                # information_schema.recycle_bin
                import copy

                sel = copy.copy(stmt)
                sel.table = f"{info.INFORMATION_SCHEMA}.recycle_bin"
                return info.execute(self, sel)
            if info.is_pg_catalog(stmt.table):
                return info.execute_pg_catalog(self, stmt)
            if stmt.from_subquery is not None:
                # before the information_schema bare-name rewrite: the
                # derived table's alias is not a system table name
                return self._execute_from_subquery(stmt)
            if (
                stmt.table
                and "." not in stmt.table
                and self.current_db == info.INFORMATION_SCHEMA
            ):
                import copy

                sel = copy.copy(stmt)
                sel.table = f"{info.INFORMATION_SCHEMA}.{stmt.table}"
                return info.execute(self, sel)
            if stmt.table is not None:
                vdb, vname = self._split_name(stmt.table)
                if self.catalog.get_engine(vdb, vname) == "view":
                    if stmt.joins:
                        raise Unsupported(
                            "views cannot participate in JOIN yet")
                    return self._execute_view_select(
                        stmt, self.catalog.get_table(vdb, vname))
                for j in stmt.joins:
                    jdb, jname = self._split_name(j.table)
                    if self.catalog.get_engine(jdb, jname) == "view":
                        raise Unsupported(
                            "views cannot participate in JOIN yet")
            return self.engine.execute_select(stmt)
        if isinstance(stmt, Tql):
            return self._execute_tql(stmt)
        if isinstance(stmt, Explain):
            return self._explain(stmt)
        if isinstance(stmt, CreateTable):
            return self._create_table(stmt)
        if isinstance(stmt, CreateView):
            return self._create_view(stmt)
        if isinstance(stmt, DropView):
            return self._drop_view(stmt)
        if isinstance(stmt, CreateDatabase):
            self.catalog.create_database(stmt.name, stmt.if_not_exists)
            return QueryResult([], [], affected_rows=1)
        if isinstance(stmt, Insert):
            return self._insert(stmt)
        if isinstance(stmt, Delete):
            return self._delete(stmt)
        if isinstance(stmt, DropTable):
            return self._drop_table(stmt)
        if isinstance(stmt, DropDatabase):
            tables = self.catalog.drop_database(stmt.name, stmt.if_exists)
            for t in tables:
                for rid in t.region_ids:
                    self.regions.drop_region(rid)
            return QueryResult([], [], affected_rows=1)
        if isinstance(stmt, AlterTable):
            return self._alter_table(stmt)
        if isinstance(stmt, Admin):
            return self._admin(stmt)
        if isinstance(stmt, ShowDatabases):
            from greptimedb_tpu.meta import information_schema as info

            names = self.catalog.list_databases() + [info.INFORMATION_SCHEMA]
            rows = [[d] for d in sorted(names) if _like(d, stmt.like)]
            return QueryResult(["Databases"], rows)
        if isinstance(stmt, ShowTables):
            from greptimedb_tpu.meta import information_schema as info

            db = stmt.database or self.current_db
            if db == info.INFORMATION_SCHEMA:
                rows = [[n] for n in sorted(info._TABLES)
                        if _like(n, stmt.like)]
                if stmt.full:
                    rows = [r + ["SYSTEM VIEW"] for r in rows]
            else:
                infos = [t for t in self.catalog.list_tables(db)
                         if _like(t.name, stmt.like)]
                if stmt.full:
                    rows = [[t.name,
                             "VIEW" if t.engine == "view" else "BASE TABLE"]
                            for t in infos]
                else:
                    rows = [[t.name] for t in infos]
            if stmt.full:
                return QueryResult(["Tables", "Table_type"], rows)
            return QueryResult(["Tables"], rows)
        from greptimedb_tpu.query.ast import ShowColumns, ShowIndex

        if isinstance(stmt, ShowColumns):
            # MySQL SHOW COLUMNS shape (reference show_columns,
            # src/query/src/sql.rs)
            view = self._table_view(stmt.table)
            rows = []
            for c in view.schema:
                key = ("PRI" if c.is_tag
                       else "TIME INDEX" if c.semantic is SemanticType.TIMESTAMP
                       else "")
                rows.append([c.name, c.dtype.value,
                             "Yes" if c.nullable else "No", key])
            return QueryResult(["Field", "Type", "Null", "Key"], rows)
        if isinstance(stmt, ShowIndex):
            view = self._table_view(stmt.table)
            rows = []
            seq = 1
            for c in view.schema:
                if c.is_tag:
                    rows.append([stmt.table, "PRIMARY", seq, c.name,
                                 "greptime-inverted-index-v1"])
                    seq += 1
                elif c.semantic is SemanticType.TIMESTAMP:
                    rows.append([stmt.table, "TIME INDEX", 1, c.name, ""])
            return QueryResult(
                ["Table", "Key_name", "Seq_in_index", "Column_name",
                 "Index_type"], rows)
        if isinstance(stmt, ShowCreateTable):
            return self._show_create(stmt)
        if isinstance(stmt, DescribeTable):
            return self._describe(stmt)
        if isinstance(stmt, Use):
            from greptimedb_tpu.meta import information_schema as info

            if stmt.database != info.INFORMATION_SCHEMA and not (
                self.catalog.database_exists(stmt.database)
            ):
                from greptimedb_tpu.errors import DatabaseNotFound

                raise DatabaseNotFound(stmt.database)
            self.current_db = stmt.database
            return QueryResult([], [])
        if isinstance(stmt, TruncateTable):
            db, name = self._split_name(stmt.table)
            if self.metric_engine.is_logical(db, name):
                raise Unsupported(
                    "TRUNCATE on a metric-engine logical table (the region "
                    "is shared across metrics)"
                )
            for region in self._regions_of(stmt.table):
                region.truncate()
            # lineage checks would catch the staleness lazily; eager
            # invalidation frees the fingerprint bytes now
            self.engine.executor.fulltext_cache.invalidate_table(name)
            self.engine.executor.fulltext_cache.invalidate_table(stmt.table)
            return QueryResult([], [], affected_rows=0)
        if isinstance(stmt, (CreateFlow, DropFlow, ShowFlows)):
            return self._flow_statement(stmt)
        from greptimedb_tpu.query.ast import Copy, Kill, SetVar, ShowProcesslist

        if isinstance(stmt, ShowProcesslist):
            cols = ["Id", "Catalog", "Schemas", "Query", "Client",
                    "Frontend", "Elapsed Time"]
            rows = []
            for t in self.processes.list():
                q = t.query if stmt.full else t.query[:100]
                rows.append([
                    str(t.id), "greptime", t.database, q, t.client,
                    self.processes.server_addr,
                    round(t.elapsed_ms / 1000, 3),
                ])
            return QueryResult(cols, rows)
        if isinstance(stmt, Kill):
            try:
                pid = self.processes.parse_id(stmt.process_id)
            except ValueError:
                raise InvalidArguments(
                    f"invalid process id {stmt.process_id!r}"
                ) from None
            found = self.processes.kill(pid)
            if not found:
                raise InvalidArguments(f"no running query with id {pid}")
            return QueryResult([], [], affected_rows=1)

        if isinstance(stmt, Copy):
            return self._copy(stmt)
        if isinstance(stmt, SetVar):
            if stmt.name in ("time_zone", "timezone"):
                self.set_timezone(stmt.value)
            # other variables (names, sql_mode, ...) are accepted as no-ops
            # for client compatibility, like the reference
            return QueryResult([], [])
        raise Unsupported(f"statement {type(stmt).__name__}")

    # ---- DDL (journaled procedures, reference ddl_manager.rs:99) -------
    def _create_table(self, stmt: CreateTable) -> QueryResult:
        from greptimedb_tpu.errors import DatabaseNotFound, TableAlreadyExists
        from greptimedb_tpu.meta.ddl import CreateTableProcedure

        db, name = self._split_name(stmt.name)
        schema = schema_from_create(stmt)
        if stmt.engine == "metric":
            return self._create_metric_table(db, name, stmt, schema)
        if stmt.engine == "file":
            loc = stmt.options.get("location")
            if not loc:
                raise InvalidArguments(
                    "CREATE EXTERNAL TABLE needs WITH (location='...')"
                )
            stmt.options.setdefault("format", "parquet")
        # argument errors surface here, before anything is journaled.
        # This exists-precheck + submit sequence is atomic in-process:
        # every DDL statement executes under self._lock (_sql_locked), so
        # two CREATE IF NOT EXISTS cannot interleave between the check
        # and the procedure's catalog commit.
        if not self.catalog.database_exists(db):
            raise DatabaseNotFound(db)
        if self.catalog.table_exists(db, name):
            if stmt.if_not_exists:
                return QueryResult([], [], affected_rows=0)
            raise TableAlreadyExists(f"{db}.{name}")
        # append-mode table (reference WITH (append_mode='true'), the
        # log/trace model): every row kept, no (series, ts) dedup
        append = str(stmt.options.get("append_mode", "")).lower() in (
            "true", "1")
        # retention (reference WITH (ttl='7d')): validated here so a bad
        # duration fails the statement, enforced at flush/compaction
        ttl_ms = None
        if stmt.options.get("ttl"):
            from greptimedb_tpu.utils.config import parse_duration_ms

            try:
                ttl_ms = parse_duration_ms(stmt.options["ttl"])
            except ValueError as e:
                raise InvalidArguments(str(e)) from None
        self.procedures.submit(CreateTableProcedure(state={
            "db": db, "name": name, "schema": schema.to_dict(),
            "engine": stmt.engine, "options": stmt.options,
            "partition_exprs": stmt.partitions,
            "partition_columns": stmt.partition_columns,
            "num_regions": max(len(stmt.partitions), 1),
            "append_mode": append,
            "ttl_ms": ttl_ms,
        }))
        return QueryResult([], [], affected_rows=0)

    def _create_metric_table(self, db, name, stmt, schema) -> QueryResult:
        """CREATE TABLE … ENGINE = metric: the DDL front of the metric
        engine (reference src/metric-engine create.rs — physical tables
        own storage, logical tables multiplex on via row modifiers).
        Here ALL logical tables share the ONE default physical region
        (storage/metric_engine.py), so a named physical table becomes a
        catalog alias over its region ids."""
        from greptimedb_tpu.errors import TableAlreadyExists
        from greptimedb_tpu.storage.metric_engine import (
            PHYSICAL_TABLE, physical_schema,
        )

        if self.catalog.table_exists(db, name):
            if stmt.if_not_exists:
                return QueryResult([], [], affected_rows=0)
            raise TableAlreadyExists(f"{db}.{name}")
        if "physical_metric_table" in stmt.options:
            self.metric_engine.physical_region(db)
            if name != PHYSICAL_TABLE:
                info = self.catalog.create_table(
                    db, name, physical_schema(),
                    engine="metric_physical", if_not_exists=True,
                )
                if info is not None:
                    phys = self.catalog.get_table(db, PHYSICAL_TABLE)
                    info.region_ids = list(phys.region_ids)
                    self.catalog.update_table(info)
            return QueryResult([], [], affected_rows=0)
        # logical table (WITH (on_physical_table = '…'): any physical
        # name accepted — the shared region holds them all)
        ti = schema.time_index
        fields = [c for c in schema if c.semantic is SemanticType.FIELD]
        if (ti is None or ti.name != "ts" or len(fields) != 1
                or fields[0].name != "val"):
            raise Unsupported(
                "metric-engine logical tables use (tags…, ts TIMESTAMP "
                "TIME INDEX, val DOUBLE) column names")
        tags = [c.name for c in schema if c.is_tag]
        self.metric_engine.ensure_logical(name, tags, db)
        return QueryResult([], [], affected_rows=0)

    def _create_view(self, stmt: CreateView) -> QueryResult:
        """CREATE [OR REPLACE] VIEW: the definition SQL persists in the
        catalog (reference src/common/meta/src/ddl/create_view.rs — view
        metadata in kv, expanded at plan time)."""
        db, name = self._split_name(stmt.name)
        if self.catalog.table_exists(db, name):
            existing = self.catalog.get_table(db, name)
            if stmt.or_replace and existing.engine == "view":
                self.catalog.drop_table(db, name)
            elif stmt.if_not_exists:
                return QueryResult([], [], affected_rows=0)
            else:
                raise TableAlreadyExists(f"{db}.{name}")
        # cycle guard at definition time: a view may not reference itself
        parsed = parse_sql(stmt.definition)
        if not parsed or not isinstance(parsed[0], (Select,)) and (
                parsed[0].__class__.__name__ != "Union"):
            raise InvalidArguments("view definition must be a SELECT")
        self.catalog.create_table(
            db, name, Schema(tuple()), engine="view",
            options={"definition": stmt.definition}, num_regions=0,
        )
        return QueryResult([], [], affected_rows=0)

    def _drop_view(self, stmt: DropView) -> QueryResult:
        db, name = self._split_name(stmt.name)
        try:
            info = self.catalog.get_table(db, name)
        except TableNotFound:
            if stmt.if_exists:
                return QueryResult([], [], affected_rows=0)
            raise
        if info.engine != "view":
            raise InvalidArguments(f"{db}.{name} is a table, not a view")
        self.catalog.drop_table(db, name)
        return QueryResult([], [], affected_rows=0)

    _VIEW_DEPTH_LIMIT = 16

    def _execute_view_select(self, sel: Select, vinfo) -> QueryResult:
        """Expand a view at query time: evaluate the stored definition
        through the full dispatch (views over views, unions, joins all
        work), stage the result as an ephemeral in-memory region, and run
        the outer SELECT over it."""
        import dataclasses

        inner_res = self._run_staged_inner(
            lambda: self.execute_statement(
                parse_sql(vinfo.options["definition"])[0]),
            "view expansion")
        staged = dataclasses.replace(
            sel, table="__view__", table_alias=None,
        )
        return self._select_over_staged(staged, inner_res)

    def _run_staged_inner(self, run, what: str):
        """Depth-guarded inner evaluation shared by view expansion and
        derived tables (one definition of the recursion bookkeeping)."""
        depth = getattr(self._proc_local, "view_depth", 0)
        if depth >= self._VIEW_DEPTH_LIMIT:
            raise PlanError(
                f"{what} exceeded depth {self._VIEW_DEPTH_LIMIT}")
        self._proc_local.view_depth = depth + 1
        try:
            return run()
        finally:
            self._proc_local.view_depth = depth

    def _select_over_staged(self, staged_sel, inner_res) -> QueryResult:
        """Stage a QueryResult into an ephemeral region and run the outer
        select over it — the shared tail of view expansion and derived
        tables."""
        from greptimedb_tpu.query.engine import (
            QueryEngine, SingleTableProvider,
        )
        from greptimedb_tpu.query.join import stage_result_region

        region = stage_result_region(inner_res)
        inner = QueryEngine(SingleTableProvider(region, self.timezone))
        inner.dispatch = self.execute_statement
        return inner.execute_select(staged_sel)

    def _execute_from_subquery(self, sel) -> QueryResult:
        """Derived table: FROM (SELECT …) [alias] — evaluate the inner
        select through the full dispatch, stage its rows into an
        ephemeral region (SAME machinery as view expansion,
        query/join.stage_result_region), and run the outer select over
        it.  The reference gets this from DataFusion's subquery planning
        (src/query/src/planner.rs); here staging keeps the outer query
        on the normal device path."""
        if sel.joins:
            raise Unsupported("derived tables cannot participate in JOIN")
        import dataclasses

        inner_res = self._run_staged_inner(
            lambda: self.execute_statement(sel.from_subquery),
            "subquery nesting")
        return self._select_over_staged(
            dataclasses.replace(sel, from_subquery=None), inner_res)

    def _drop_table(self, stmt: DropTable) -> QueryResult:
        from greptimedb_tpu.storage.metric_engine import PHYSICAL_TABLE

        for full in stmt.names:
            db, name = self._split_name(full)
            try:
                existing = self.catalog.get_table(db, name)
            except TableNotFound:
                existing = None
            if existing is not None and existing.engine == "metric":
                # logical metric table: drop METADATA only — the region is
                # shared with every other metric (its rows are reclaimed by
                # compaction GC later, like the reference's metric engine)
                self.catalog.drop_table(db, name, stmt.if_exists)
                self.cache.invalidate_region(
                    -(1 << 50) - existing.table_id
                )
                continue
            if existing is not None and existing.engine == "metric_physical":
                logical = [t for t in self.catalog.list_tables(db)
                           if t.engine == "metric"]
                if logical:
                    raise InvalidArguments(
                        f"cannot drop {PHYSICAL_TABLE}: {len(logical)} logical "
                        "metric tables still reference it"
                    )
            if existing is None:
                if not stmt.if_exists:
                    raise TableNotFound(f"{db}.{name}")
                continue
            if existing.engine == "view":
                raise InvalidArguments(
                    f"{db}.{name} is a view — use DROP VIEW")
            if existing.engine == "file":
                view = getattr(self, "_file_views", {}).pop((db, name), None)
                if view is not None:
                    self.cache.invalidate_region(view.region_id)
            from greptimedb_tpu.meta.ddl import DropTableProcedure

            self.procedures.submit(DropTableProcedure(state={
                "db": db, "name": name, "if_exists": stmt.if_exists,
            }))
            self.engine.executor.fulltext_cache.invalidate_table(name)
            self.engine.executor.fulltext_cache.invalidate_table(full)
        return QueryResult([], [], affected_rows=1)

    def _admin(self, stmt) -> QueryResult:
        """ADMIN functions (reference src/common/function/src/admin/):
        flush/compact by table or region, and reconciliation."""
        import json as _json

        from greptimedb_tpu.meta.reconciliation import reconcile_standalone

        name, args = stmt.func, list(stmt.args)

        def result(payload) -> QueryResult:
            return QueryResult(
                [f"ADMIN {name}"],
                [[payload if isinstance(payload, str)
                  else _json.dumps(payload)]],
                column_types=["String"])

        if name in ("flush_table", "compact_table"):
            if len(args) != 1:
                raise InvalidArguments(f"ADMIN {name}(table_name)")
            for region in self._regions_of(str(args[0])):
                region.flush()
                if name == "compact_table":
                    region.compact()
            return result("ok")
        if name in ("flush_region", "compact_region"):
            if len(args) != 1:
                raise InvalidArguments(f"ADMIN {name}(region_id)")
            try:
                rid = int(args[0])
            except (TypeError, ValueError):
                raise InvalidArguments(
                    f"ADMIN {name}: region id must be an integer")
            region = self.regions.regions.get(rid)
            if region is None:
                raise TableNotFound(f"region {args[0]} not open")
            region.flush()
            if name == "compact_region":
                region.compact()
            return result("ok")
        if name == "undrop_table":
            # restore the NEWEST recycle-bin entry (reference recycle bin,
            # src/common/meta/src/ddl/drop_table.rs + purge_dropped_table)
            if len(args) != 1:
                raise InvalidArguments("ADMIN undrop_table(table_name)")
            dbname, tname = self._split_name(str(args[0]))
            if self.catalog.table_exists(dbname, tname):
                raise TableAlreadyExists(
                    f"{dbname}.{tname} exists; cannot undrop over it")
            entry = self.catalog.recycle_take(dbname, tname)
            if entry is None:
                raise TableNotFound(
                    f"{dbname}.{tname} is not in the recycle bin")
            info = TableInfo.from_dict(entry["info"])
            self.catalog.restore_table(info)
            for rid in info.region_ids:
                self.regions.open_region(rid)
            return result("ok")
        if name == "purge_recycle_bin":
            # hard-delete recycled tables older than the given duration
            # (default: everything)
            from greptimedb_tpu.utils.config import parse_duration_ms

            import time as _time

            older_ms = parse_duration_ms(str(args[0])) if args else 0
            cutoff = int(_time.time() * 1000) - (older_ms or 0)
            purged = 0
            for entry in self.catalog.recycle_list():
                if entry["dropped_at_ms"] > cutoff:
                    continue
                for rid in entry["info"].get("region_ids", []):
                    try:
                        self.regions.drop_region(rid)
                    except Exception:  # noqa: BLE001 — already gone
                        pass
                self.catalog.recycle_remove(entry["key"])
                purged += 1
            return result({"purged_tables": purged})
        if name == "reconcile_table":
            if not args:
                raise InvalidArguments(
                    "ADMIN reconcile_table(table_name[, strategy])")
            db, table = self._split_name(str(args[0]))
            strategy = str(args[1]) if len(args) > 1 else "use_latest"
            return result(reconcile_standalone(
                self, db, table, strategy=strategy))
        if name == "reconcile_database":
            db = str(args[0]) if args else self.current_db
            strategy = str(args[1]) if len(args) > 1 else "use_latest"
            return result(reconcile_standalone(self, db, strategy=strategy))
        if name == "reconcile_catalog":
            strategy = str(args[0]) if args else "use_latest"
            return result(reconcile_standalone(self, strategy=strategy))
        raise Unsupported(f"ADMIN function {name}")

    _ALTERABLE_OPTIONS = {"ttl", "append_mode", "compaction_window",
                          "comment"}

    def _alter_table_options(self, db: str, name: str, info,
                             stmt: AlterTable) -> QueryResult:
        """ALTER TABLE SET/UNSET table options (reference
        src/store-api/src/mito_engine_options.rs), journaled through
        AlterOptionsProcedure so a crash between the catalog commit and
        the per-region manifest commits resumes instead of diverging."""
        from greptimedb_tpu.meta.ddl import AlterOptionsProcedure
        from greptimedb_tpu.utils.config import parse_duration_ms

        new_opts = dict(info.options)
        if stmt.action == "set_options":
            for k in (stmt.options or {}):
                if k not in self._ALTERABLE_OPTIONS:
                    raise Unsupported(f"ALTER TABLE SET {k!r}")
            new_opts.update(stmt.options or {})
        else:
            if stmt.name not in self._ALTERABLE_OPTIONS:
                raise Unsupported(f"ALTER TABLE UNSET {stmt.name!r}")
            new_opts.pop(stmt.name, None)
        for k in ("ttl", "compaction_window"):  # fail BEFORE any commit
            if new_opts.get(k):
                try:
                    parse_duration_ms(new_opts[k])
                except ValueError as e:
                    raise InvalidArguments(str(e)) from None
        self.procedures.submit(AlterOptionsProcedure(state={
            "db": db, "name": name, "options": new_opts,
        }))
        return QueryResult([], [], affected_rows=0)

    def _alter_table(self, stmt: AlterTable) -> QueryResult:
        db, name = self._split_name(stmt.table)
        info = self.catalog.get_table(db, name)
        if stmt.action == "add_column":
            cd = stmt.column
            dtype = ConcreteDataType.parse(cd.type_name)
            new_schema = info.schema.with_added_column(
                ColumnSchema(cd.name, dtype, SemanticType.FIELD, cd.nullable)
            )
        elif stmt.action == "drop_column":
            new_schema = info.schema.with_dropped_column(stmt.name)
        elif stmt.action == "rename":
            self.catalog.rename_table(db, name, stmt.name)
            return QueryResult([], [], affected_rows=0)
        elif stmt.action in ("set_options", "unset_option"):
            return self._alter_table_options(db, name, info, stmt)
        else:
            raise Unsupported(f"alter {stmt.action}")
        from greptimedb_tpu.meta.ddl import AlterTableProcedure

        self.procedures.submit(AlterTableProcedure(state={
            "db": db, "name": name, "new_schema": new_schema.to_dict(),
        }))
        return QueryResult([], [], affected_rows=0)

    # ---- DML -----------------------------------------------------------
    def _insert(self, stmt: Insert) -> QueryResult:
        if stmt.select is not None:
            # INSERT INTO … SELECT: evaluate through the full dispatch
            # (views/information_schema work), then insert positionally
            import dataclasses as _dc

            res = self.execute_statement(stmt.select)
            if not res.rows:
                return QueryResult([], [], affected_rows=0)
            return self._insert(_dc.replace(
                stmt, rows=[list(r) for r in res.rows], select=None))
        db, name = self._split_name(stmt.table)
        try:
            if self.catalog.get_table(db, name).engine == "file":
                raise Unsupported("external (file engine) tables are read-only")
        except TableNotFound:
            pass
        if self.metric_engine.is_logical(db, name):
            # logical metric table: route through the metric engine's
            # multiplexing write (physical region + __metric__ tag)
            info = self.catalog.get_table(db, name)
            _columns, data = insert_rows_to_columns(
                stmt, info.schema, self.timezone)
            tags = [c.name for c in info.schema if c.is_tag]
            cols = dict(data)
            cols["__tags__"] = [t for t in tags if t in cols]
            cols["__fields__"] = ["val"]
            n = self.metric_engine.write(name, cols, db)
            return QueryResult([], [], affected_rows=n)
        regions = self._regions_of(stmt.table)
        schema = regions[0].schema
        columns, data = insert_rows_to_columns(stmt, schema, self.timezone)
        ts_name = schema.time_index.name
        if len(regions) == 1:
            regions[0].write(data)
        else:
            # route rows to partitions (reference split_rows, manager.rs:232)
            import numpy as np

            from greptimedb_tpu.parallel.partition import split_rows

            rule = self._partition_rule(stmt.table)
            cols_np = {c: np.asarray(v, dtype=object) for c, v in data.items()}
            parts = split_rows(rule, cols_np, len(stmt.rows))
            for pidx, row_idx in parts.items():
                if pidx >= len(regions):
                    raise InvalidArguments(
                        f"partition index {pidx} out of range"
                    )
                sub = {c: [data[c][i] for i in row_idx] for c in columns}
                regions[pidx].write(sub)
        if self.flow_engine.flows:
            # batching flows: mark dirty windows and re-evaluate synchronously
            # (the reference defers via eval_schedule; standalone runs inline)
            appendable = all(
                getattr(r, "last_write_appendable", True) for r in regions
            )
            self.flow_engine.on_write(stmt.table, data[ts_name], data=data,
                                      appendable=appendable)
            self.flow_engine.run_all()
        return QueryResult([], [], affected_rows=len(stmt.rows))

    def _delete(self, stmt: Delete) -> QueryResult:
        """DELETE by exact key conjunction (tags + ts), the mito semantic."""
        regions = self._regions_of(stmt.table)
        region = regions[0]
        ctx = TableContext(region.schema, region.encoders, self.timezone)
        from greptimedb_tpu.query.ast import BinaryOp, Column, Literal

        eq: dict[str, object] = {}
        general = False

        def visit(e):
            nonlocal general
            if isinstance(e, BinaryOp) and e.op == "AND":
                visit(e.left)
                visit(e.right)
            elif (
                isinstance(e, BinaryOp)
                and e.op == "="
                and isinstance(e.left, Column)
                and isinstance(e.right, Literal)
            ):
                eq[ctx.resolve(e.left.name)] = e.right.value
            else:
                general = True  # arbitrary predicate: resolve via a scan

        if stmt.where is None:
            raise Unsupported("DELETE without WHERE (use TRUNCATE)")
        visit(stmt.where)
        ts_name = region.schema.time_index.name
        if general or ts_name not in eq:
            # general predicate (or key-only conjunction): resolve the
            # matching (primary key, ts) rows through the query engine,
            # then tombstone each — the reference reaches the same via
            # DataFusion resolving the WHERE into delete keys
            return self._delete_by_scan(stmt, regions, ctx, ts_name)
        data = {k: [ctx.ts_literal(v) if k == ts_name else v] for k, v in eq.items()}
        if len(regions) == 1:
            region.delete(data)
        else:
            import numpy as np

            from greptimedb_tpu.parallel.partition import split_rows

            rule = self._partition_rule(stmt.table)
            cols_np = {c: np.asarray(v, dtype=object) for c, v in data.items()}
            parts = split_rows(rule, cols_np, 1)
            for pidx in parts:
                regions[pidx].delete(data)
        return QueryResult([], [], affected_rows=1)

    def _delete_by_scan(self, stmt, regions, ctx, ts_name) -> QueryResult:
        """DELETE with an arbitrary WHERE: select the matching
        (tags…, ts) keys, then issue key-exact tombstones."""
        from greptimedb_tpu.query.ast import Column, Select, SelectItem

        tag_names = [c.name for c in regions[0].schema.tag_columns]
        cols = tag_names + [ts_name]
        sel = Select(
            items=[SelectItem(Column(c)) for c in cols],
            table=stmt.table,
            where=stmt.where,
        )
        res = self.engine.execute_select(sel)
        if not res.rows:
            return QueryResult([], [], affected_rows=0)
        data = {c: [row[i] for row in res.rows]
                for i, c in enumerate(cols)}
        if len(regions) == 1:
            regions[0].delete(data)
        else:
            from greptimedb_tpu.parallel.partition import split_rows

            rule = self._partition_rule(stmt.table)
            cols_np = {c: np.asarray(v, dtype=object)
                       for c, v in data.items()}
            parts = split_rows(rule, cols_np, len(res.rows))
            for pidx, idx in parts.items():
                regions[pidx].delete(
                    {c: [data[c][i] for i in idx] for c in cols})
        return QueryResult([], [], affected_rows=len(res.rows))

    # ---- COPY TO/FROM ---------------------------------------------------
    def _copy(self, stmt) -> QueryResult:
        """COPY table TO/FROM file (reference copy_table_{to,from}; formats
        from src/common/datasource: parquet, csv, json)."""
        import numpy as np
        import pyarrow as pa

        fmt = stmt.options.get("format", "parquet").lower()
        view = self._table_view(stmt.table)
        schema = view.schema
        if stmt.direction == "to":
            host = view.scan_host()
            cols = {}
            for c in schema:
                arr = host[c.name]
                cols[c.name] = pa.array(
                    arr.astype(object) if arr.dtype == object else arr,
                    type=c.to_arrow().type,
                )
            table = pa.table(cols)
            if fmt == "parquet":
                import pyarrow.parquet as pq

                pq.write_table(table, stmt.path)
            elif fmt == "csv":
                import pyarrow.csv as pacsv

                pacsv.write_csv(table, stmt.path)
            elif fmt == "json":
                import json as _json

                with open(stmt.path, "w") as f:
                    for row in table.to_pylist():
                        f.write(_json.dumps(row, default=str) + "\n")
            else:
                raise Unsupported(f"COPY format {fmt}")
            return QueryResult([], [], affected_rows=table.num_rows)
        # COPY FROM
        if fmt == "parquet":
            import pyarrow.parquet as pq

            table = pq.read_table(stmt.path)
        elif fmt == "csv":
            import pyarrow.csv as pacsv

            table = pacsv.read_csv(stmt.path)
        elif fmt == "json":
            import json as _json

            rows = [
                _json.loads(line)
                for line in open(stmt.path)
                if line.strip()
            ]
            table = pa.Table.from_pylist(rows)
        else:
            raise Unsupported(f"COPY format {fmt}")
        # reuse RecordBatch.from_arrow: it already handles null-int widening
        # (fill before to_numpy) and unit casts (batch.py) — re-implementing
        # the conversion here caused both classes of bug
        from greptimedb_tpu.datatypes.batch import RecordBatch
        from greptimedb_tpu.datatypes.schema import Schema as _Schema

        present = [c for c in schema if c.name in table.column_names]
        sub_schema = _Schema(tuple(present))
        casted = []
        for c in present:
            arr = table.column(c.name)
            want_type = c.to_arrow().type
            if arr.type != want_type:
                arr = arr.cast(want_type)  # incl. timestamp UNIT casts
            casted.append(arr)
        rb = RecordBatch.from_arrow(
            pa.Table.from_arrays(casted, schema=sub_schema.to_arrow()),
            sub_schema,
        )
        data: dict = {}
        for c in present:
            col = rb.columns[c.name]
            null = rb.nulls.get(c.name)
            if c.dtype.is_timestamp:
                col = col.astype("int64")
            elif null is not None and c.dtype.is_float:
                col = col.copy()
                col[null] = np.nan
            data[c.name] = col
        if table.num_rows:
            regions = self._regions_of(stmt.table)
            if len(regions) == 1:
                regions[0].write(data)
            else:
                from greptimedb_tpu.parallel.partition import split_rows

                cols_np = {k: np.asarray(v, dtype=object)
                           for k, v in data.items()}
                parts = split_rows(self._partition_rule(stmt.table), cols_np,
                                   table.num_rows)
                for pidx, row_idx in parts.items():
                    if pidx >= len(regions):
                        raise InvalidArguments(
                            f"partition index {pidx} out of range"
                        )
                    sub = {k: [data[k][i] for i in row_idx] for k in data}
                    regions[pidx].write(sub)
            if self.flow_engine.flows:
                ts_name = schema.time_index.name
                appendable = all(
                    getattr(r, "last_write_appendable", True)
                    for r in regions
                )
                self.flow_engine.on_write(stmt.table, data[ts_name],
                                          data=data, appendable=appendable)
                self.flow_engine.run_all()
        return QueryResult([], [], affected_rows=table.num_rows)

    # ---- introspection -------------------------------------------------
    def _describe(self, stmt: DescribeTable) -> QueryResult:
        db, name = self._split_name(stmt.table)
        info = self.catalog.get_table(db, name)
        rows = []
        for c in info.schema:
            semantic = {
                SemanticType.TAG: "TAG",
                SemanticType.FIELD: "FIELD",
                SemanticType.TIMESTAMP: "TIMESTAMP",
            }[c.semantic]
            rows.append([
                c.name, c.dtype.value,
                "PRI" if c.semantic in (SemanticType.TAG, SemanticType.TIMESTAMP) else "",
                "YES" if c.nullable else "NO",
                c.default, semantic,
            ])
        return QueryResult(
            ["Column", "Type", "Key", "Null", "Default", "Semantic Type"], rows
        )

    def _show_create(self, stmt: ShowCreateTable) -> QueryResult:
        db, name = self._split_name(stmt.table)
        info = self.catalog.get_table(db, name)
        if info.engine == "view" or stmt.view:
            if info.engine != "view":
                raise InvalidArguments(f"{db}.{name} is a table, not a view")
            text = (f'CREATE VIEW "{info.name}" AS '
                    f'{info.options.get("definition", "")}')
            return QueryResult(["View", "Create View"],
                               [[info.name, text]])
        lines = [f"CREATE TABLE IF NOT EXISTS \"{info.name}\" ("]
        defs = []
        for c in info.schema:
            d = f'  "{c.name}" {c.dtype.value.upper()}'
            if not c.nullable:
                d += " NOT NULL"
            defs.append(d)
        ti = info.schema.time_index
        if ti is not None:
            defs.append(f'  TIME INDEX ("{ti.name}")')
        tags = [c.name for c in info.schema.tag_columns]
        if tags:
            defs.append("  PRIMARY KEY (" + ", ".join(f'"{t}"' for t in tags) + ")")
        lines.append(",\n".join(defs))
        lines.append(")")
        lines.append(f"ENGINE={info.engine}")
        if info.options:
            opts = ", ".join(f"{k}='{v}'" for k, v in info.options.items())
            lines.append(f"WITH ({opts})")
        return QueryResult(["Table", "Create Table"], [[info.name, "\n".join(lines)]])

    def _explain(self, stmt: Explain) -> QueryResult:
        if isinstance(stmt.inner, Select):
            text = self.engine.explain(stmt.inner)
        elif isinstance(stmt.inner, Tql):
            text = f"TQL {stmt.inner.command} (promql planning)"
        else:
            text = f"{type(stmt.inner).__name__}"
        rows = [["logical_plan (tpu)", text]]
        if stmt.analyze and isinstance(stmt.inner, Select):
            from greptimedb_tpu.utils.tracing import TRACER, render_span_tree

            # EXPLAIN ANALYZE (reference DistAnalyzeExec): run the query and
            # report per-stage wall times + row counts.  Statements that
            # arrived through the scheduler carry their queue wait/batch
            # columns into the analyze lines (sched_wait_ms/sched_batch)
            # plus a dedicated scheduler row below; direct db.sql keeps
            # the seed format byte-for-byte.
            metrics: dict = {}
            sched = getattr(self._proc_local, "sched_info", None)
            if sched:
                metrics.update(sched)
            self.engine.execute_select(stmt.inner, metrics=metrics)
            # run once more for warm (compiled) numbers — the first run may
            # include XLA compilation.  With the tracer on, this warm run's
            # span tree is surfaced as its own row (per-stage wall/device
            # ms next to the layout=/jit_cache annotations above).
            span_mark = TRACER.mark() if TRACER.enabled else 0
            warm: dict = {}
            self.engine.execute_select(stmt.inner, metrics=warm)
            lines = [
                f"{k}: {metrics[k]} (warm: {warm.get(k, '-')})"
                for k in metrics
            ]
            rows.append(["analyze (cold vs warm ms)", "\n".join(lines)])
            if sched:
                st = self.scheduler.stats()
                rows.append([
                    "analyze (scheduler)",
                    f"wait_ms: {sched.get('sched_wait_ms', 0)}\n"
                    f"batch: {sched.get('sched_batch', 1)}\n"
                    f"queue_depth: {st['queue_depth']}\n"
                    f"batches: {st['batches']} "
                    f"(queries {st['batched_queries']}, "
                    f"largest {st['largest_batch']})\n"
                    f"shed: {st['shed']}",
                ])
            if TRACER.enabled:
                tree = render_span_tree(TRACER.since(span_mark))
                if tree:
                    rows.append(["analyze (span tree, warm run)", tree])
                tid = TRACER.current_trace_id()
                if tid:
                    # the id the whole statement's spans carry (external
                    # traceparent or the fresh id minted at the protocol
                    # layer) — feed it to the Jaeger API after a flush
                    rows.append(["analyze (trace_id)", tid])
        return QueryResult(["plan_type", "plan"], rows)

    # ---- TQL / flows (wired in later milestones) -----------------------
    def _execute_tql(self, stmt: Tql) -> QueryResult:
        from greptimedb_tpu.promql.engine import execute_tql

        return execute_tql(self, stmt)

    def _flow_statement(self, stmt) -> QueryResult:
        from greptimedb_tpu.flow.engine import handle_flow_statement

        return handle_flow_statement(self, stmt)


def _like(name: str, pattern: str | None) -> bool:
    if pattern is None:
        return True
    import fnmatch

    return fnmatch.fnmatch(name, pattern.replace("%", "*").replace("_", "?"))
