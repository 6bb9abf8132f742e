"""End-to-end query telemetry: registry exposition, span-instrumented
engines, device-phase metrics, cache/HBM gauges, zero-overhead disabled
tracing.

Reference counterparts: per-crate metric registries exported at /metrics
(src/servers/src/http.rs:944), common-telemetry span instrumentation
(src/common/telemetry), slow-query recorder (common-event-recorder).
"""

import json
import re

import pytest

from greptimedb_tpu.standalone import GreptimeDB
from greptimedb_tpu.utils.telemetry import (
    REGISTRY, Counter, Gauge, Histogram, Registry,
)
from greptimedb_tpu.utils.tracing import TRACER, render_span_tree


@pytest.fixture
def db():
    d = GreptimeDB()
    d.sql("CREATE TABLE cpu (h STRING, ts TIMESTAMP(3) TIME INDEX, "
          "v DOUBLE, PRIMARY KEY (h))")
    d.sql("INSERT INTO cpu VALUES ('a', 1000, 1.0), ('b', 2000, 2.0), "
          "('a', 3000, 3.0), ('b', 4000, 4.0)")
    yield d
    d.close()


# ---------------------------------------------------------------------------
# Prometheus text exposition
# ---------------------------------------------------------------------------

class TestExposition:
    def test_label_value_escaping(self):
        r = Registry()
        c = r.counter("esc_total", "escapes", labels=("q",))
        c.labels('he said "hi"\\path\nnext').inc()
        text = r.render()
        assert 'q="he said \\"hi\\"\\\\path\\nnext"' in text
        assert "\n q=" not in text  # the newline never splits the line

    def test_help_escaping(self):
        r = Registry()
        r.counter("h_total", "line1\nline2 \\ backslash").inc()
        line = next(l for l in r.render().splitlines()
                    if l.startswith("# HELP h_total"))
        assert line == "# HELP h_total line1\\nline2 \\\\ backslash"

    def test_type_lines(self):
        r = Registry()
        r.counter("a_total").inc()
        r.gauge("b_bytes").set(2)
        r.histogram("c_seconds").observe(0.1)
        text = r.render()
        assert "# TYPE a_total counter" in text
        assert "# TYPE b_bytes gauge" in text
        assert "# TYPE c_seconds histogram" in text
        assert "a_total 1.0" in text
        assert "b_bytes 2" in text

    def test_histogram_cumulative_buckets_end_in_inf(self):
        r = Registry()
        h = r.histogram("lat_seconds", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 0.5, 5.0, 50.0):
            h.observe(v)
        lines = r.render().splitlines()
        buckets = [l for l in lines if l.startswith("lat_seconds_bucket")]
        # cumulative counts, +Inf last and equal to the observation count
        assert buckets == [
            'lat_seconds_bucket{le="0.1"} 1',
            'lat_seconds_bucket{le="1.0"} 3',
            'lat_seconds_bucket{le="10.0"} 4',
            'lat_seconds_bucket{le="+Inf"} 5',
        ]
        assert "lat_seconds_count 5" in lines
        assert any(l.startswith("lat_seconds_sum") for l in lines)

    def test_gauge_set_function_pull(self):
        r = Registry()
        g = r.gauge("pull_bytes")
        state = {"v": 7.0}
        g.set_function(lambda: state["v"])
        assert "pull_bytes 7.0" in r.render()
        state["v"] = 9.0
        assert "pull_bytes 9.0" in r.render()

    def test_export_samples_histogram_explosion(self):
        # the self-import sample shape: cumulative _bucket rows with an
        # le label ending in +Inf, plus _sum/_count — same layout the
        # OTLP ingest path produces, so histogram_quantile just works
        r = Registry()
        h = r.histogram("lat_seconds", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(5.0)
        samples = {(n, tuple(sorted(lab.items()))): v
                   for n, lab, v in r.export_samples()}
        assert samples[("lat_seconds_bucket", (("le", "0.1"),))] == 1.0
        assert samples[("lat_seconds_bucket", (("le", "1.0"),))] == 1.0
        assert samples[("lat_seconds_bucket", (("le", "+Inf"),))] == 2.0
        assert samples[("lat_seconds_count", ())] == 2.0
        assert samples[("lat_seconds_sum", ())] == pytest.approx(5.05)

    def test_registry_value_reader(self):
        r = Registry()
        c = r.counter("v_total", labels=("k",))
        c.labels("x").inc(3)
        assert r.value("v_total", ("x",)) == 3.0
        assert r.value("v_total", ("missing",)) == 0.0
        assert r.value("absent_total") == 0.0


# ---------------------------------------------------------------------------
# Tier-1 registry static check (duplicate registrations + name convention)
# ---------------------------------------------------------------------------

class TestRegistryStaticCheck:
    def test_collision_detection(self):
        r = Registry()
        r.counter("dup_total")
        r.gauge("dup_total")  # kind mismatch
        r.counter("lbl_total", labels=("a",))
        r.counter("lbl_total", labels=("b",))  # label-set mismatch
        assert len(r.collisions) == 2

    def test_process_registry_is_clean(self):
        # import every metric-registering module, then walk the REGISTRY:
        # no conflicting re-registrations, and every metric/label name
        # follows the Prometheus [a-z_][a-z0-9_]* convention
        import greptimedb_tpu.compile.service  # noqa: F401
        import greptimedb_tpu.flow.engine  # noqa: F401
        import greptimedb_tpu.meta.cluster  # noqa: F401
        import greptimedb_tpu.meta.migration  # noqa: F401
        import greptimedb_tpu.parallel.dist  # noqa: F401
        import greptimedb_tpu.promql.engine  # noqa: F401
        import greptimedb_tpu.query.physical  # noqa: F401
        import greptimedb_tpu.rpc.frontend  # noqa: F401
        import greptimedb_tpu.servers.http  # noqa: F401
        import greptimedb_tpu.servers.protocols  # noqa: F401
        import greptimedb_tpu.servers.tcp  # noqa: F401
        import greptimedb_tpu.serving.scheduler  # noqa: F401
        import greptimedb_tpu.standalone  # noqa: F401
        import greptimedb_tpu.storage.cache  # noqa: F401
        import greptimedb_tpu.storage.wal  # noqa: F401
        import greptimedb_tpu.utils.chaos  # noqa: F401
        import greptimedb_tpu.utils.memory  # noqa: F401

        # the convention/collision logic lives in the analyzer's hygiene
        # pass now (single source of truth): check_registry is the
        # RUNTIME twin of the static GL-T001/T002/T003 checks, applied
        # to whatever actually registered (dynamic names included)
        from greptimedb_tpu.analysis.passes.hygiene import check_registry

        assert check_registry(REGISTRY) == []
        for m in REGISTRY._metrics.values():
            assert isinstance(m, (Counter, Gauge, Histogram))
        # the serving scheduler's first-class metric surface must exist
        # by import (not lazily on first query): /metrics scrapes on an
        # idle instance still show the queue/batch/admission families
        for required in (
            "greptime_scheduler_queue_depth",
            "greptime_scheduler_wait_seconds",
            "greptime_scheduler_batch_size",
            "greptime_scheduler_batches_total",
            "greptime_scheduler_batched_queries_total",
            "greptime_scheduler_shed_total",
            "greptime_scheduler_executed_total",
            "greptime_scheduler_admitted_total",
            "greptime_scheduler_rejected_total",
            "greptime_scheduler_tenant_inflight",
        ):
            assert required in REGISTRY._metrics, required
        # the query-compiler subsystem's surface (persistent compile
        # cache hits/misses/persists, AOT warmup outcomes, fused
        # dispatches) exists by import for the same reason
        for required in (
            "greptime_compile_cache_events_total",
            "greptime_compile_xla_builds_total",
            "greptime_compile_fused_dispatch_total",
            "greptime_compile_warmup_total",
            "greptime_compile_cache_disk_bytes",
        ):
            assert required in REGISTRY._metrics, required
        # the vectorized ingest pipeline's metric surface likewise exists
        # by import: wire decode (rows/bytes/batches/parse-phase seconds,
        # the object-decode pin the hot path holds at 0) and the WAL
        # group-commit batch/fsync accounting
        for required in (
            "greptime_ingest_rows_total",
            "greptime_ingest_bytes_total",
            "greptime_ingest_batches_total",
            "greptime_ingest_parse_seconds",
            "greptime_ingest_object_decode_rows_total",
            "greptime_ingest_wal_batch_size",
            "greptime_ingest_wal_fsyncs_total",
        ):
            assert required in REGISTRY._metrics, required
        # the durability surface (corruption triage, quarantine, repair)
        # likewise exists by import: an idle /metrics scrape must already
        # expose the counters operators alert on
        import greptimedb_tpu.storage.durability  # noqa: F401

        for required in (
            "greptime_durability_corruption_total",
            "greptime_durability_quarantined_total",
            "greptime_durability_repaired_total",
        ):
            assert required in REGISTRY._metrics, required
        # the fulltext fingerprint index: candidates/verified/matched
        # (false-positive ratio), selectivity, per-path query counts and
        # resident bytes
        import greptimedb_tpu.fulltext.resident  # noqa: F401

        for required in (
            "greptime_fulltext_candidates_total",
            "greptime_fulltext_verified_total",
            "greptime_fulltext_matched_total",
            "greptime_fulltext_scanned_total",
            "greptime_fulltext_queries_total",
            "greptime_fulltext_indexed_values_total",
            "greptime_fulltext_resident_bytes",
        ):
            assert required in REGISTRY._metrics, required
        # the SLO observatory + idle economy (serving/slo.py, serving/
        # idle.py): sketches, error budgets, burn rates, and the
        # idle-grant ledger — the surface the self-monitor loop gates on
        import greptimedb_tpu.serving.idle  # noqa: F401
        import greptimedb_tpu.serving.slo  # noqa: F401

        for required in (
            "greptime_slo_latency",
            "greptime_slo_budget_remaining",
            "greptime_slo_burn_rate",
            "greptime_idle_granted_total",
            "greptime_idle_elapsed_seconds_total",
            "greptime_idle_starved_total",
            "greptime_idle_throttled_total",
        ):
            assert required in REGISTRY._metrics, required

    def test_self_export_table_naming(self):
        # the self-import loop (utils/selfmonitor.py) names tables after
        # registry metrics: every name must round-trip through the OTLP
        # normalizer unchanged, and the prometheus-style histogram
        # explosion (_bucket/_sum/_count) must not collide with any
        # other registered metric's table
        import greptimedb_tpu.flow.engine  # noqa: F401
        import greptimedb_tpu.parallel.dist  # noqa: F401
        import greptimedb_tpu.promql.engine  # noqa: F401
        import greptimedb_tpu.query.physical  # noqa: F401
        import greptimedb_tpu.servers.http  # noqa: F401
        import greptimedb_tpu.servers.tcp  # noqa: F401
        import greptimedb_tpu.serving.scheduler  # noqa: F401
        import greptimedb_tpu.standalone  # noqa: F401
        import greptimedb_tpu.storage.cache  # noqa: F401
        import greptimedb_tpu.utils.memory  # noqa: F401
        from greptimedb_tpu.analysis.passes.hygiene import check_registry
        from greptimedb_tpu.servers.otlp import _norm

        # delegated to the hygiene pass's runtime twin: histogram
        # explosion collisions + the OTLP normalizer round-trip
        assert check_registry(REGISTRY, norm=_norm) == []


# ---------------------------------------------------------------------------
# Instance identity + workload gauges
# ---------------------------------------------------------------------------

class TestInstanceMetrics:
    def test_build_info_and_uptime(self):
        from greptimedb_tpu import __version__

        text = REGISTRY.render()
        assert f'greptime_build_info{{version="{__version__}"' in text
        m = re.search(r"(?m)^greptime_process_uptime_seconds (\S+)$", text)
        assert m and float(m.group(1)) >= 0.0
        assert "greptime_process_start_time_seconds" in text

    def test_workload_hbm_gauges(self, db):
        text = REGISTRY.render()
        for wl in ("ingest", "device_cache", "layout_cache", "promql_cache"):
            assert f'greptime_memory_workload_used_bytes{{workload="{wl}"}}' \
                in text
        # pull-mode: the gauge reads the same number usage() reports
        used = db.memory.usage()["device_cache"]["used_bytes"]
        assert REGISTRY.value("greptime_memory_workload_used_bytes",
                              ("device_cache",)) == float(used)

    def test_runtime_metrics_carries_identity(self, db):
        r = db.sql("SELECT metric_name FROM information_schema.runtime_metrics"
                   " WHERE metric_name LIKE 'greptime_build%'")
        assert ["greptime_build_info"] in r.rows


# ---------------------------------------------------------------------------
# Query latency histograms + cache counters in the registry
# ---------------------------------------------------------------------------

class TestQueryTelemetry:
    def test_engine_histograms(self, db):
        sql0 = REGISTRY.value("greptime_query_duration_seconds", ("sql",))
        tql0 = REGISTRY.value("greptime_query_duration_seconds", ("promql",))
        db.sql("SELECT h, avg(v) FROM cpu GROUP BY h")
        db.sql("TQL EVAL (0, 10, '5s') avg(cpu)")
        assert REGISTRY.value(
            "greptime_query_duration_seconds", ("sql",)) > sql0
        assert REGISTRY.value(
            "greptime_query_duration_seconds", ("promql",)) > tql0

    def test_device_phase_split(self, db):
        # a never-seen GROUP BY shape forces a jit-cache miss → the
        # compile phase is observed; EXPLAIN ANALYZE then shows the
        # steady-state device wait next to the jit_cache annotation
        c0 = REGISTRY.value("greptime_query_stage_seconds",
                            ("xla_compile",))
        w0 = REGISTRY.value("greptime_query_stage_seconds",
                            ("device_wait",))
        db.sql("SELECT h, min(v), max(v), count(v) FROM cpu GROUP BY h")
        assert REGISTRY.value("greptime_query_stage_seconds",
                              ("xla_compile",)) > c0
        # the wait for the device is a stage of every query, asked for
        # or not: it is where the result leaves the device
        assert REGISTRY.value("greptime_query_stage_seconds",
                              ("device_wait",)) == w0 + 1
        r = db.sql("EXPLAIN ANALYZE SELECT h, min(v), max(v), count(v) "
                   "FROM cpu GROUP BY h")
        analyze = r.rows[1][1]
        assert "jit_cache:" in analyze
        assert "device_wait_ms:" in analyze

    def test_promql_stage_histogram(self, db):
        s0 = REGISTRY.value("greptime_query_stage_seconds", ("selection",))
        db.sql("TQL EVAL (0, 10, '5s') sum by(h) (cpu)")
        assert REGISTRY.value(
            "greptime_query_stage_seconds", ("selection",)) > s0
        assert "greptime_promql_stage_seconds" not in REGISTRY.render()
        assert "greptime_device_phase_seconds" not in REGISTRY.render()

    def test_promql_cache_counters_mirror_registry(self, db):
        ev = "greptime_cache_events_total"
        h0 = REGISTRY.value(ev, ("promql", "selection", "hit"))
        db.sql("TQL EVAL (0, 10, '5s') avg(cpu)")
        db.sql("TQL EVAL (0, 10, '5s') avg(cpu)")  # warm: selection hit
        assert REGISTRY.value(ev, ("promql", "selection", "hit")) > h0
        # instance counters and registry mirror move together
        assert db.promql_cache.hits["selection"] > 0

    def test_region_cache_counters(self, db):
        ev = "greptime_cache_events_total"
        before = REGISTRY.value(ev, ("region_device", "table", "hit"))
        db.sql("SELECT * FROM cpu ORDER BY ts LIMIT 1")
        db.sql("SELECT * FROM cpu ORDER BY ts LIMIT 1")
        assert REGISTRY.value(ev, ("region_device", "table", "hit")) > before

    def test_flow_tick_metrics(self, db):
        db.sql("CREATE FLOW f_cnt SINK TO cpu_hourly AS "
               "SELECT h, count(v) AS c, date_trunc('hour', ts) AS hr "
               "FROM cpu GROUP BY h, hr")
        r0 = REGISTRY.value("greptime_flow_rows_total", ("f_cnt",))
        db.sql("INSERT INTO cpu VALUES ('c', 5000, 5.0)")
        assert REGISTRY.value("greptime_flow_rows_total", ("f_cnt",)) >= r0
        assert REGISTRY.value("greptime_flow_tick_duration_seconds",
                              ("f_cnt", "streaming")) > 0


# ---------------------------------------------------------------------------
# Zero-overhead disabled tracing (pins the seed fast path)
# ---------------------------------------------------------------------------

class TestDisabledTracingZeroOverhead:
    def test_no_span_objects_allocated(self, db):
        assert not TRACER.enabled

        def boom(*a, **k):  # any span() call while disabled is a bug
            raise AssertionError("span allocated with tracer disabled")

        TRACER.span = boom
        try:
            db.sql("SELECT h, avg(v) FROM cpu GROUP BY h")
            db.sql("TQL EVAL (0, 10, '5s') sum by(h) (cpu)")
        finally:
            del TRACER.__dict__["span"]
        assert TRACER._spans == []

    def test_explain_analyze_seed_format_unchanged(self, db):
        r = db.sql("EXPLAIN ANALYZE SELECT h, avg(v) FROM cpu GROUP BY h")
        assert r.column_names == ["plan_type", "plan"]
        # seed shape: exactly the logical plan + one analyze row, no
        # span-tree row, every analyze line `key: value (warm: value)`
        assert [row[0] for row in r.rows] == [
            "logical_plan (tpu)", "analyze (cold vs warm ms)"]
        for line in r.rows[1][1].splitlines():
            assert re.match(r"^[a-z_]+: .+ \(warm: .+\)$", line), line


# ---------------------------------------------------------------------------
# Span-instrumented engines (tracer on)
# ---------------------------------------------------------------------------

class TestSpanTrees:
    @pytest.fixture
    def traced(self):
        TRACER.configure(enabled=True)
        TRACER.drain()
        yield TRACER
        TRACER.disable()

    def test_sql_stage_spans(self, db, traced):
        db.sql("SELECT h, avg(v) FROM cpu GROUP BY h")
        names = {s["name"] for s in traced.drain()}
        assert {"sql", "execute_statement", "parse", "optimize", "plan",
                "execute", "materialize"} <= names

    def test_promql_stage_spans(self, db, traced):
        db.sql("TQL EVAL (0, 10, '5s') sum by(h) (cpu)")
        names = {s["name"] for s in traced.drain()}
        # the fused chain (compile/fused.py) replaces the window-kernel +
        # eager-reduce pair with ONE fused_kernel span; every unfusable
        # shape keeps the window_kernel span
        assert {"selection", "sort_layout", "group_agg",
                "label_decode"} <= names
        assert "fused_kernel" in names or "window_kernel" in names

    def test_promql_stage_spans_unfused(self, db, traced, ineligible):
        with ineligible("fusion"):
            db.sql("TQL EVAL (0, 10, '5s') sum by(h) (cpu)")
        names = {s["name"] for s in traced.drain()}
        assert {"selection", "sort_layout", "window_kernel", "group_agg",
                "label_decode"} <= names

    def test_explain_analyze_span_tree_row(self, db, traced):
        r = db.sql("EXPLAIN ANALYZE SELECT h, avg(v) FROM cpu GROUP BY h")
        labels = [row[0] for row in r.rows]
        assert "analyze (span tree, warm run)" in labels
        tree = r.rows[labels.index("analyze (span tree, warm run)")][1]
        assert "execute" in tree and "materialize" in tree
        assert re.search(r"execute: \d+\.\d+ ms", tree)

    def test_mark_since_windowing(self, traced):
        with traced.span("a"):
            pass
        m = traced.mark()
        with traced.span("b"):
            pass
        assert [s["name"] for s in traced.since(m)] == ["b"]
        # drain moves the window; since() never resurrects drained spans
        traced.drain()
        assert traced.since(m) == []

    def test_render_span_tree_nesting(self, traced):
        with traced.span("outer"):
            with traced.span("inner"):
                pass
        tree = render_span_tree(traced.drain())
        lines = tree.splitlines()
        assert lines[0].startswith("outer:")
        assert lines[1].startswith("  inner:")


# ---------------------------------------------------------------------------
# Slow-query stage self-reporting
# ---------------------------------------------------------------------------

class TestSlowQueryStages:
    def test_sql_and_tql_stage_breakdown(self, db):
        db.sql("TQL EVAL (0, 10, '5s') avg(cpu)")  # warm the kernel class
        db.slow_query_threshold_ms = 0.0001
        try:
            db.sql("SELECT h, avg(v) FROM cpu GROUP BY h")
            db.sql("TQL EVAL (0, 10, '5s') avg(cpu)")
        finally:
            db.slow_query_threshold_ms = 0.0
        r = db.sql("SELECT query, stages FROM greptime_private.slow_queries")
        by_query = {q: s for q, s in r.rows}
        sql_stages = json.loads(
            by_query["SELECT h, avg(v) FROM cpu GROUP BY h"])
        assert "plan_ms" in sql_stages and "device_exec_ms" in sql_stages
        tql_stages = json.loads(by_query["TQL EVAL (0, 10, '5s') avg(cpu)"])
        # fused chain reports its one dispatch as fused_kernel; unfused
        # (PLAN_FUSION=off, unfusable shapes) keeps window_kernel
        assert ("promql_fused_kernel_ms" in tql_stages
                or "promql_window_kernel_ms" in tql_stages)
        assert "promql_selection_ms" in tql_stages
