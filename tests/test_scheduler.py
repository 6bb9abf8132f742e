"""Concurrent serving layer: scheduler, admission, batching, priorities.

Covers the PR's acceptance surface: bit-exact batched-vs-solo parity,
per-tenant quota rejection + fallback, priority ordering under a
saturated (background-occupied) pool, and deadline shedding.

Reference counterpart: the frontend's admission/flow-control surface
(GreptimeDB limits concurrent queries per frontend and rejects with
RateLimited); the cross-query stacked dispatch is the Theseus
(arXiv 2508.05029) / Data Path Fusion (arXiv 2605.10511) move.
"""

import threading
import time

import numpy as np
import pytest

from greptimedb_tpu.errors import (
    Cancelled, DeadlineExceeded, RateLimited, ResourcesExhausted,
)
from greptimedb_tpu.standalone import GreptimeDB
from greptimedb_tpu.utils.telemetry import REGISTRY

pytestmark = pytest.mark.concurrency

T0 = 1451606400000  # TSBS epoch
HOSTS = 6
HOURS = 3
STEP_MS = 10_000


def _mk_db():
    db = GreptimeDB()
    db.sql(
        "CREATE TABLE cpu (hostname STRING, ts TIMESTAMP(3) TIME INDEX, "
        "usage_user DOUBLE, usage_system DOUBLE, PRIMARY KEY (hostname))"
    )
    rows = []
    rng = np.random.default_rng(7)
    vals = rng.uniform(0, 100, size=(HOSTS, HOURS * 360, 2))
    for h in range(HOSTS):
        for i in range(HOURS * 360):
            rows.append(
                f"('host_{h}', {T0 + i * STEP_MS}, "
                f"{vals[h, i, 0]:.3f}, {vals[h, i, 1]:.3f})"
            )
    for c in range(0, len(rows), 1000):
        db.sql("INSERT INTO cpu VALUES " + ",".join(rows[c:c + 1000]))
    return db


def _window_sql(hour_lo: int, hours: int = 1) -> str:
    lo = T0 + hour_lo * 3600_000
    hi = lo + hours * 3600_000
    return (
        "SELECT hostname, date_trunc('hour', ts) AS hour, "
        "avg(usage_user), avg(usage_system) FROM cpu "
        f"WHERE ts >= {lo} AND ts < {hi} GROUP BY hostname, hour"
    )


@pytest.fixture(scope="module")
def db():
    d = _mk_db()
    yield d
    d.close()


# ---------------------------------------------------------------------------
# Batched vs solo: bit-exact parity
# ---------------------------------------------------------------------------

class TestBatchParity:
    def test_stacked_dispatch_bit_exact(self, db):
        sched = db.scheduler
        assert sched is not None
        # warm the solo path (and the layout cache) per window class
        solo = {w: db.sql(_window_sql(w)) for w in range(HOURS)}
        b0 = REGISTRY.value("greptime_scheduler_batched_queries_total")
        results: dict[int, object] = {}
        errors: list = []

        def client(i):
            try:
                results[i] = sched.submit(_window_sql(i % HOURS))
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        # repeat until at least one real multi-query dispatch happened —
        # closed-loop saturation forms batches, but a fast machine can
        # drain the queue before neighbors arrive
        for _ in range(20):
            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(12)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert not errors, errors
            for i, res in results.items():
                want = solo[i % HOURS]
                assert res.column_names == want.column_names
                # BIT-exact: float cells compare with ==, not approx
                assert res.rows == want.rows
            if REGISTRY.value(
                    "greptime_scheduler_batched_queries_total") > b0:
                break
        assert REGISTRY.value(
            "greptime_scheduler_batched_queries_total") > b0, (
            "no stacked dispatch formed across 20 saturated rounds")
        assert db.scheduler.largest_batch > 1

    def test_tag_filtered_stacked_dispatch_bit_exact(self, db):
        """where_series extension: concurrent windows identical up to
        the tag filter (`hostname = 'host_i'`) coalesce into one stacked
        dispatch — each member's predicate rides in as a traced
        per-series mask — and every member's rows stay bit-exact vs its
        solo run."""
        sched = db.scheduler

        def q(i):
            lo = T0
            hi = lo + 3600_000
            return (
                "SELECT hostname, date_trunc('hour', ts) AS hour, "
                "avg(usage_user), avg(usage_system) FROM cpu "
                f"WHERE hostname = 'host_{i}' AND ts >= {lo} "
                f"AND ts < {hi} GROUP BY hostname, hour"
            )

        from greptimedb_tpu.query.physical import DISPATCH_STATS

        solo = {i: db.sql(q(i)) for i in range(HOSTS)}
        b0 = DISPATCH_STATS["grid_batch"]
        results: dict[int, object] = {}
        errors: list = []

        def client(i):
            try:
                results[i] = sched.submit(q(i % HOSTS))
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        for _ in range(20):
            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(12)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert not errors, errors
            for i, res in results.items():
                want = solo[i % HOSTS]
                assert res.column_names == want.column_names
                assert res.rows == want.rows  # BIT-exact
            if DISPATCH_STATS["grid_batch"] > b0:
                break
        assert DISPATCH_STATS["grid_batch"] > b0, (
            "no tag-filtered stacked dispatch formed in 20 rounds")

    def test_engine_batch_entry_bit_exact(self, db):
        """Direct engine-level parity: execute_select_batch vs
        execute_select on identical Selects, no scheduler timing luck."""
        from greptimedb_tpu.query.parser import parse_sql

        sels = [parse_sql(_window_sql(w))[0] for w in (0, 1, 2, 1)]
        solo = [db.engine.execute_select(s) for s in sels]
        batched = db.engine.execute_select_batch(sels)
        assert batched is not None
        for b, s in zip(batched, solo):
            assert b.column_names == s.column_names
            assert b.rows == s.rows

    def test_batch_falls_back_on_mixed_shapes(self, db):
        """Different window lengths (different bucket-count class) must
        refuse the stacked dispatch, not mis-batch."""
        from greptimedb_tpu.query.parser import parse_sql

        sels = [parse_sql(_window_sql(0, 1))[0],
                parse_sql(_window_sql(0, 2))[0]]
        assert db.engine.execute_select_batch(sels) is None

    def test_batch_refuses_views_and_system_tables(self, db):
        from greptimedb_tpu.query.parser import parse_sql

        s = parse_sql("SELECT table_name FROM information_schema.tables")[0]
        assert db.sql_batch([("q", s, None, None),
                             ("q", s, None, None)]) is None


# ---------------------------------------------------------------------------
# Per-tenant admission
# ---------------------------------------------------------------------------

class TestTenantAdmission:
    def test_rate_quota_rejects_then_refills(self, db):
        sched = db.scheduler
        sched.admission.set_quota("rate_t", qps=20.0, burst=2)
        assert sched.submit("SELECT 1", tenant="rate_t").rows == [[1]]
        with pytest.raises(RateLimited) as ei:
            for _ in range(8):  # burst is 2; the loop must trip the limit
                sched.submit("SELECT 1", tenant="rate_t")
        assert "over rate quota" in str(ei.value)
        # fallback: tokens refill (20 qps), the tenant recovers
        time.sleep(0.15)
        assert sched.submit("SELECT 1", tenant="rate_t").rows == [[1]]
        assert REGISTRY.value("greptime_scheduler_rejected_total",
                              ("rate_t", "rate")) >= 1

    def test_memory_quota_rejects_via_workload_manager(self, db):
        sched = db.scheduler
        sched.admission.set_quota(
            "mem_t", mem_bytes=sched.query_est_bytes // 2)
        with pytest.raises(ResourcesExhausted) as ei:
            sched.submit("SELECT 1", tenant="mem_t")
        assert "over memory quota" in str(ei.value)
        # the budget registered as a first-class workload: same pull
        # gauges and usage surface as every other workload
        usage = db.memory.usage()
        assert "tenant:mem_t" in usage
        assert usage["tenant:mem_t"]["rejected"] >= 1
        # fallback: lift the quota, the tenant is served again
        sched.admission.set_quota("mem_t", mem_bytes=None)
        assert sched.submit("SELECT 1", tenant="mem_t").rows == [[1]]

    def test_concurrency_quota_and_try_admit_fallback(self, db):
        sched = db.scheduler
        sched.admission.set_quota("cc_t", max_inflight=1)
        sched.admission.admit("cc_t")  # occupy the only slot
        try:
            with pytest.raises(RateLimited):
                sched.admission.admit("cc_t")
            assert sched.admission.try_admit("cc_t") is False
        finally:
            sched.admission.release("cc_t")
        assert sched.admission.try_admit("cc_t") is True
        sched.admission.release("cc_t")

    def test_queue_full_backpressure(self, db):
        from greptimedb_tpu.serving.scheduler import QueryScheduler

        s = QueryScheduler(db, workers=1, max_queue=1, batching=False)
        release = threading.Event()
        started = threading.Event()

        def blocker():
            started.set()
            release.wait(5)
            return "done"

        t = threading.Thread(
            target=lambda: s.submit_fn(blocker, priority="background"))
        t.start()
        started.wait(5)
        # worker busy; one entry fills the queue, the next is rejected
        t2 = threading.Thread(
            target=lambda: s.submit_fn(lambda: None,
                                       priority="background"))
        t2.start()
        deadline = time.time() + 5
        while time.time() < deadline:
            with s._cond:
                if sum(len(q) for q in s._queues.values()) >= 1:
                    break
            time.sleep(0.005)
        with pytest.raises(ResourcesExhausted) as ei:
            s.submit_fn(lambda: None, priority="background")
        assert "queue full" in str(ei.value)
        release.set()
        t.join(5)
        t2.join(5)
        s.stop()


# ---------------------------------------------------------------------------
# Priorities + scan-pool preemption
# ---------------------------------------------------------------------------

class TestPriorities:
    def test_interactive_overtakes_background_queue(self, db):
        """One worker, occupied: later-submitted interactive work must
        complete before earlier-queued background work."""
        from greptimedb_tpu.serving.scheduler import QueryScheduler

        s = QueryScheduler(db, workers=1, batching=False)
        order: list[str] = []
        release = threading.Event()
        started = threading.Event()

        def occupy():
            started.set()
            release.wait(5)

        threads = [threading.Thread(
            target=lambda: s.submit_fn(occupy, priority="background"))]
        threads[0].start()
        started.wait(5)

        def bg():
            order.append("background")

        def ia():
            order.append("interactive")

        for fn, prio in ((bg, "background"), (bg, "background"),
                         (ia, "interactive")):
            threads.append(threading.Thread(
                target=lambda f=fn, p=prio: s.submit_fn(f, priority=p)))
            threads[-1].start()
        deadline = time.time() + 5
        while time.time() < deadline:
            with s._cond:
                if sum(len(q) for q in s._queues.values()) == 3:
                    break
            time.sleep(0.005)
        release.set()
        for t in threads:
            t.join(5)
        assert order[0] == "interactive", order
        s.stop()

    def test_scan_pool_yields_to_interactive(self, db):
        """A background-priority thread narrows the cold-scan decode pool
        to 1 while interactive queries wait (cooperative preemption)."""
        from greptimedb_tpu.serving import scheduler as sched_mod
        from greptimedb_tpu.storage.scan import scan_threads

        assert scan_threads(8) >= 1
        sched_mod._worker_local.priority = "background"
        try:
            with sched_mod._wait_lock:
                sched_mod._interactive_waiting += 1
            try:
                assert sched_mod.background_should_yield() is True
                assert scan_threads(8) == 1
            finally:
                with sched_mod._wait_lock:
                    sched_mod._interactive_waiting -= 1
            assert sched_mod.background_should_yield() is False
            assert scan_threads(8) >= 1
        finally:
            sched_mod._worker_local.priority = None

    def test_statement_classification(self, db):
        from greptimedb_tpu.query.parser import parse_sql

        s = db.scheduler
        assert s.classify(parse_sql("SELECT 1")) == "interactive"
        assert s.classify(parse_sql("INSERT INTO cpu VALUES "
                                    "('x', 1, 1.0, 1.0)")) == "normal"
        assert s.classify(parse_sql(
            "COPY cpu TO '/tmp/x.parquet'")) == "background"
        assert s.classify(parse_sql("ADMIN flush_table('cpu')")) == (
            "background")


# ---------------------------------------------------------------------------
# Deadline shedding
# ---------------------------------------------------------------------------

class TestAdaptiveLinger:
    """Round-13 satellite: the group-commit linger scales with observed
    same-class pressure instead of firing at a constant — idle traffic
    must never pay it."""

    def test_effective_linger_scales_with_pressure(self, db):
        from greptimedb_tpu.serving.scheduler import QueryScheduler

        s = QueryScheduler(db, workers=1)
        s.linger_ms = 100.0
        ceiling = 0.1
        # idle: nothing else in flight -> zero linger
        s._sqlish_inflight["interactive"] = 1
        assert s._effective_linger_s("interactive", 1) == 0.0
        # light contention: a fraction of the ceiling
        s._sqlish_inflight["interactive"] = 1 + s.max_batch // 2
        mid = s._effective_linger_s("interactive", 1)
        assert 0.0 < mid < ceiling
        # saturation (a full batch's worth pending): the whole ceiling
        s._sqlish_inflight["interactive"] = 1 + s.max_batch
        assert s._effective_linger_s("interactive", 1) == ceiling
        # other priority classes don't bleed into the signal
        assert s._effective_linger_s("background", 1) == 0.0
        s._sqlish_inflight["interactive"] = 0
        s.stop()

    def test_idle_path_p50_pays_no_linger(self, db):
        """A lone sequential client must not wait out the linger window:
        with a deliberately huge ceiling (250 ms), 9 solo submits whose
        p50 stays far under it prove the idle path dispatches
        immediately."""
        from greptimedb_tpu.serving.scheduler import QueryScheduler

        s = QueryScheduler(db, workers=1)
        s.linger_ms = 250.0
        try:
            s.submit(_window_sql(0))  # warm compile/layout outside timing
            lat_ms = []
            for _ in range(9):
                t0 = time.perf_counter()
                s.submit(_window_sql(0))
                lat_ms.append((time.perf_counter() - t0) * 1000)
            p50 = sorted(lat_ms)[len(lat_ms) // 2]
            assert p50 < 250.0, (
                f"idle p50 {p50:.1f} ms >= linger ceiling — idle traffic "
                f"is paying the group-commit linger")
        finally:
            s.stop()


class TestDeadlines:
    def test_queued_entry_sheds_at_deadline(self, db):
        from greptimedb_tpu.serving.scheduler import QueryScheduler

        s = QueryScheduler(db, workers=1, batching=False)
        release = threading.Event()
        started = threading.Event()

        def occupy():
            started.set()
            release.wait(5)

        t = threading.Thread(
            target=lambda: s.submit_fn(occupy, priority="background"))
        t.start()
        started.wait(5)
        shed0 = REGISTRY.value("greptime_scheduler_shed_total",
                               ("interactive",))
        err: list = []

        def victim():
            try:
                s.submit("SELECT 1", timeout_s=0.05)
            except Exception as e:  # noqa: BLE001
                err.append(e)

        v = threading.Thread(target=victim)
        v.start()
        time.sleep(0.2)  # deadline passes while queued
        release.set()
        v.join(5)
        t.join(5)
        assert err and isinstance(err[0], DeadlineExceeded), err
        assert REGISTRY.value("greptime_scheduler_shed_total",
                              ("interactive",)) > shed0
        s.stop()

    def test_stop_cancels_queued(self, db):
        from greptimedb_tpu.serving.scheduler import QueryScheduler

        s = QueryScheduler(db, workers=1, batching=False)
        release = threading.Event()
        started = threading.Event()
        errs: list = []

        def occupy():
            started.set()
            release.wait(5)

        t = threading.Thread(
            target=lambda: s.submit_fn(occupy, priority="background"))
        t.start()
        started.wait(5)

        def queued():
            try:
                s.submit("SELECT 1")
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        q = threading.Thread(target=queued)
        q.start()
        deadline = time.time() + 5
        while time.time() < deadline:
            with s._cond:
                if s._queues["interactive"]:
                    break
            time.sleep(0.005)
        release.set()
        s.stop()
        q.join(5)
        t.join(5)
        assert errs and isinstance(errs[0], Cancelled)


# ---------------------------------------------------------------------------
# Observability surface
# ---------------------------------------------------------------------------

class TestObservability:
    def test_explain_analyze_scheduler_row(self, db):
        r = db.scheduler.submit("EXPLAIN ANALYZE " + _window_sql(0))
        labels = [row[0] for row in r.rows]
        assert "analyze (scheduler)" in labels
        body = r.rows[labels.index("analyze (scheduler)")][1]
        assert "wait_ms" in body and "queue_depth" in body
        # the analyze metric lines carry the scheduler columns too
        analyze = r.rows[labels.index("analyze (cold vs warm ms)")][1]
        assert "sched_wait_ms" in analyze
        assert "sched_batch" in analyze

    def test_direct_sql_explain_analyze_format_unpolluted(self, db):
        """The pinned seed format: EXPLAIN ANALYZE issued directly (not
        through the scheduler) shows no scheduler rows or keys."""
        r = db.sql("EXPLAIN ANALYZE " + _window_sql(0))
        labels = [row[0] for row in r.rows]
        assert "analyze (scheduler)" not in labels
        assert "sched_wait_ms" not in r.rows[1][1]

    def test_slow_queries_scheduler_columns(self, db):
        prev = db.slow_query_threshold_ms
        db.slow_query_threshold_ms = 0.0001
        try:
            db.scheduler.submit(_window_sql(1))
        finally:
            db.slow_query_threshold_ms = prev
        r = db.sql("SELECT query, sched_wait_ms, sched_batch FROM "
                   "greptime_private.slow_queries ORDER BY ts DESC LIMIT 5")
        assert r.rows, "slow query not recorded"
        target = [row for row in r.rows if "avg(usage_user)" in row[0]]
        assert target, r.rows
        assert target[0][1] >= 0.0  # sched_wait_ms recorded
        assert target[0][2] >= 1.0  # sched_batch recorded

    def test_queue_depth_gauge_and_wait_histogram(self, db):
        db.scheduler.submit("SELECT 1")
        text = REGISTRY.render()
        assert 'greptime_scheduler_queue_depth{priority="interactive"}' in text
        assert REGISTRY.value("greptime_scheduler_wait_seconds",
                              ("interactive",)) > 0

    def test_scheduler_span_in_trace(self, db):
        from greptimedb_tpu.utils.tracing import TRACER

        try:
            TRACER.configure()
            mark = TRACER.mark()
            db.scheduler.submit("SELECT 1")
            spans = TRACER.since(mark)
            assert any(s["name"] == "scheduler" for s in spans), (
                [s["name"] for s in spans])
            sched_span = next(s for s in spans if s["name"] == "scheduler")
            assert "wait_ms" in sched_span.get("attributes", {})
        finally:
            TRACER.disable()

    def test_processlist_sees_queued_entries(self, db):
        """Entries register in the process registry at submit: SHOW
        PROCESSLIST shows them even while queued behind a busy worker."""
        from greptimedb_tpu.serving.scheduler import QueryScheduler

        s = QueryScheduler(db, workers=1, batching=False)
        release = threading.Event()
        started = threading.Event()

        def occupy():
            started.set()
            release.wait(5)

        t = threading.Thread(
            target=lambda: s.submit_fn(occupy, priority="background"))
        t.start()
        started.wait(5)
        marker = "SELECT 424242"
        q = threading.Thread(target=lambda: s.submit(marker))
        q.start()
        deadline = time.time() + 5
        seen = False
        while time.time() < deadline and not seen:
            rows = db.sql("SHOW PROCESSLIST").rows
            seen = any(marker in r[3] for r in rows)
            time.sleep(0.005)
        release.set()
        q.join(5)
        t.join(5)
        s.stop()
        assert seen, "queued entry never appeared in SHOW PROCESSLIST"


# ---------------------------------------------------------------------------
# HTTP integration: tenant header + 429 surface
# ---------------------------------------------------------------------------

class TestHttpIntegration:
    def test_http_tenant_quota_429(self):
        import json
        import urllib.error
        import urllib.request

        from greptimedb_tpu.servers import HttpServer

        d = GreptimeDB()
        srv = HttpServer(d, port=0)
        try:
            srv.start()
            assert d.scheduler is not None
            # qps low enough that the closed HTTP round-trip can never
            # refill a whole token between calls
            d.scheduler.admission.set_quota("limited", qps=0.5, burst=1)

            def call():
                req = urllib.request.Request(
                    f"http://127.0.0.1:{srv.port}/v1/sql?sql=SELECT+1",
                    headers={"x-greptime-tenant": "limited"})
                return urllib.request.urlopen(req, timeout=10)

            with call() as resp:
                assert json.load(resp)["output"][0]["records"][
                    "rows"] == [[1]]
            codes = []
            for _ in range(6):
                try:
                    with call() as resp:
                        codes.append(resp.status)
                except urllib.error.HTTPError as e:
                    codes.append(e.code)
                    body = json.load(e)
                    assert "over rate quota" in body["error"]
            assert 429 in codes, codes
        finally:
            srv.stop()
            d.close()
