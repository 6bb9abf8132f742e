"""Resident bucket-major layout cache for aligned-window range aggregation.

The derived-layout path (query/physical.py _aligned_layout +
storage/cache.py DerivedLayoutCache) must be invisible except for speed:
every test here pins its results against BOTH the dynamic-slice grid
kernel (conftest's ``ineligible("layout")``) and the row-oriented
DeviceTable path (``ineligible("grid")``).  Layout-vs-dynamic-slice
parity is asserted EXACTLY (the cached partials are the same f32 ``reshape @ ones[r]`` contraction
over identical r-element blocks); grid-vs-row parity keeps the usual f32
accumulation tolerance.
"""

import numpy as np
import pytest

from greptimedb_tpu.query.physical import DISPATCH_STATS
from greptimedb_tpu.standalone import GreptimeDB

T0 = 1700000000000  # not minute-aligned: pad_left exercises the reshape
ALIGNED_LO = T0 + 40000       # minute boundary (T0 + 40 s)
ALIGNED_HI = ALIGNED_LO + 10 * 60000

ALIGNED_SQL = (
    f"SELECT host, date_trunc('minute', ts) AS m, avg(usage), sum(mem), "
    f"count(*) FROM cpu WHERE ts >= {ALIGNED_LO} AND ts < {ALIGNED_HI} "
    f"GROUP BY host, m"
)


def _rows(res):
    return sorted(
        res.rows, key=lambda r: tuple("" if v is None else str(v) for v in r)
    )


def _assert_exact(a, b, ctx):
    assert len(a) == len(b) and len(a) > 0, (len(a), len(b), ctx)
    for ra, rb in zip(a, b):
        assert ra == rb, f"{ra} vs {rb}: {ctx}"


def _assert_close(a, b, ctx):
    assert len(a) == len(b) and len(a) > 0, (len(a), len(b), ctx)
    for ra, rb in zip(a, b):
        for va, vb in zip(ra, rb):
            if isinstance(va, float) or isinstance(vb, float):
                assert va == pytest.approx(vb, rel=2e-5, abs=1e-5), (
                    f"{va} vs {vb}: {ctx}")
            else:
                assert va == vb, f"{va} vs {vb}: {ctx}"


@pytest.fixture
def run_layout_query(ineligible):
    def run_layout_query(db, sql, expect_layout=True):
        """Run ``sql`` through the layout path and pin it against the
        dynamic-slice and row paths.  Returns the layout-path result."""
        before = DISPATCH_STATS["grid_bm"]
        r_bm = db.sql(sql)
        used = DISPATCH_STATS["grid_bm"] > before
        assert used == expect_layout, (
            f"bucket_major used={used}, expected {expect_layout}: {sql}")
        before = DISPATCH_STATS["grid_bm"]
        with ineligible("layout"):
            r_ds = db.sql(sql)
        with ineligible("grid"):
            r_row = db.sql(sql)
        assert DISPATCH_STATS["grid_bm"] == before, "a reference ran bm"
        assert r_bm.column_names == r_ds.column_names == r_row.column_names
        _assert_exact(_rows(r_bm), _rows(r_ds),
                      f"bm vs dynamic_slice: {sql}")
        _assert_close(_rows(r_bm), _rows(r_row), f"bm vs row: {sql}")
        return r_bm

    return run_layout_query


@pytest.fixture
def db(tmp_path):
    d = GreptimeDB(str(tmp_path / "lc"))
    d.sql(
        "CREATE TABLE cpu (host STRING, dc STRING, "
        "ts TIMESTAMP(3) TIME INDEX, usage DOUBLE, mem DOUBLE, "
        "PRIMARY KEY (host, dc))"
    )
    rng = np.random.default_rng(11)
    rows = []
    for k in range(240):  # 20 min @ 5 s, 6 hosts
        for h in range(6):
            u = round(float(rng.uniform(0, 100)), 3)
            m = round(float(rng.uniform(0, 64)), 3)
            rows.append(f"('h{h}','dc{h % 2}',{T0 + k * 5000},{u},{m})")
    d.sql("INSERT INTO cpu VALUES " + ",".join(rows))
    d._region_of("cpu").flush()
    yield d
    d.close()


def test_warm_queries_hit_the_layout(db, run_layout_query):
    lc = db.engine.executor.layout_cache
    run_layout_query(db, ALIGNED_SQL)
    assert lc.builds == 1 and len(lc) == 1
    hits0 = lc.hits
    r = run_layout_query(db, ALIGNED_SQL)
    assert lc.hits > hits0  # warm query served from the resident layout
    assert lc.builds == 1   # ...without rebuilding it
    assert r.num_rows == 6 * 10


def test_rolling_window_reuses_the_layout(db, run_layout_query):
    lc = db.engine.executor.layout_cache
    run_layout_query(db, ALIGNED_SQL)
    builds0 = lc.builds
    rolled = ALIGNED_SQL.replace(
        str(ALIGNED_LO), str(ALIGNED_LO + 60000)).replace(
        str(ALIGNED_HI), str(ALIGNED_HI - 60000))
    run_layout_query(db, rolled)
    # same step class, different window position: pure cache hit
    assert lc.builds == builds0 and lc.hits > 0


def test_tag_only_where_rides_the_layout(db, run_layout_query):
    sql = ALIGNED_SQL.replace("GROUP BY", "AND dc = 'dc0' GROUP BY")
    r = run_layout_query(db, sql)
    assert r.num_rows == 3 * 10  # dc0 = h0, h2, h4


def test_unaligned_window_falls_back_identical(db, run_layout_query):
    # window start off the minute boundary: dynamic-slice path serves it
    sql = ALIGNED_SQL.replace(str(ALIGNED_LO), str(ALIGNED_LO + 7000))
    before = DISPATCH_STATS["grid"]
    run_layout_query(db, sql, expect_layout=False)
    assert DISPATCH_STATS["grid"] > before  # still the grid executor


def test_minmax_falls_back(db, run_layout_query):
    sql = ALIGNED_SQL.replace("avg(usage)", "max(usage)")
    run_layout_query(db, sql, expect_layout=False)


def test_ingest_invalidates_the_stale_layout(db, run_layout_query):
    lc = db.engine.executor.layout_cache
    # wide aligned window whose last bucket still has grid headroom
    wide = (
        f"SELECT host, date_trunc('minute', ts) AS m, avg(usage), sum(mem),"
        f" count(*) FROM cpu WHERE ts >= {ALIGNED_LO} "
        f"AND ts < {T0 + 1240000} GROUP BY host, m"
    )
    r1 = run_layout_query(db, wide)
    builds0 = lc.builds
    # on-grid append (next 5s point, device-side grid extension): a stale
    # layout would keep serving the old per-bucket sums
    db.sql(f"INSERT INTO cpu VALUES ('h0','dc0',{T0 + 240 * 5000},50.0,32.0)")
    r2 = run_layout_query(db, wide)
    # generation (dicts_version) bump replaced the stale entry: exactly
    # one resident layout, rebuilt once
    assert lc.builds == builds0 + 1 and len(lc) == 1
    c1 = {(r[0], r[1]): r[4] for r in r1.rows}
    c2 = {(r[0], r[1]): r[4] for r in r2.rows}
    changed = [k for k in c2 if c2[k] != c1.get(k)]
    assert len(changed) == 1 and c2[changed[0]] == c1[changed[0]] + 1
    assert changed[0][0] == "h0"


def test_budget_reject_falls_back_identical(db, run_layout_query):
    lc = db.engine.executor.layout_cache
    run_layout_query(db, ALIGNED_SQL)
    # tightened budget: admission pressure reclaims the resident layout
    # (as WorkloadMemoryManager would), and rebuilds can no longer be
    # admitted — queries must degrade to dynamic-slice, not error
    lc.reclaim(lc.bytes)
    assert len(lc) == 0 and lc.bytes == 0
    old_cap = lc.capacity
    lc.capacity = 0
    try:
        rejects0 = lc.rejects
        run_layout_query(db, ALIGNED_SQL, expect_layout=False)
        assert lc.rejects > rejects0 and len(lc) == 0
    finally:
        lc.capacity = old_cap


def test_workload_quota_reject_falls_back(db, run_layout_query):
    # the utils/memory.py integration: a 1-byte workload quota rejects
    # the build through the memory probe; results stay correct
    run_layout_query(db, ALIGNED_SQL)
    lc = db.engine.executor.layout_cache
    lc.reclaim(lc.bytes)
    db.memory.set_quota("layout_cache", 1)
    try:
        rejects0 = lc.rejects
        run_layout_query(db, ALIGNED_SQL, expect_layout=False)
        assert lc.rejects > rejects0
    finally:
        db.memory.set_quota("layout_cache", None)
    # quota lifted: the next query re-admits and rebuilds
    builds0 = lc.builds
    run_layout_query(db, ALIGNED_SQL)
    assert lc.builds == builds0 + 1


def test_overquota_build_does_not_thrash_warm_entries(db, run_layout_query):
    # a build that can NEVER fit the workload quota must reject without
    # draining the warm entries (reclaim would evict everything and
    # still reject — pure thrash)
    lc = db.engine.executor.layout_cache
    run_layout_query(db, ALIGNED_SQL)
    assert lc.bytes > 0
    db.memory.set_quota("layout_cache", 1)
    try:
        lo2 = T0 + 120000 - (T0 % 120000)
        sql2 = (
            f"SELECT host, date_bin(INTERVAL '2 minutes', ts) AS m, "
            f"sum(usage) FROM cpu WHERE ts >= {lo2} "
            f"AND ts < {lo2 + 4 * 120000} GROUP BY host, m"
        )
        run_layout_query(db, sql2, expect_layout=False)
        assert len(lc) == 1 and lc.bytes > 0  # warm entry survived
    finally:
        db.memory.set_quota("layout_cache", None)


def test_lru_eviction_across_step_classes(db, run_layout_query):
    lc = db.engine.executor.layout_cache
    run_layout_query(db, ALIGNED_SQL)
    entry_bytes = lc.bytes
    # second step class (2-minute buckets, aligned window at a 2-min
    # boundary >= T0): both fit...
    lo2 = T0 + 120000 - (T0 % 120000)
    sql2 = (
        f"SELECT host, date_bin(INTERVAL '2 minutes', ts) AS m, sum(usage) "
        f"FROM cpu WHERE ts >= {lo2} AND ts < {lo2 + 4 * 120000} "
        f"GROUP BY host, m"
    )
    run_layout_query(db, sql2)
    assert len(lc) == 2
    # ...until the budget only holds one: the LRU entry goes
    lc.capacity = lc.bytes  # exactly current usage
    lc.admit(entry_bytes)   # next build needs room -> evicts oldest
    assert len(lc) == 1


def test_grid_lru_eviction_drops_layouts(db, run_layout_query):
    # a grid evicted under RegionCacheManager capacity pressure strands
    # its derived layouts (next build = new dicts_version, so they can
    # never hit) — eviction must drop them too
    lc = db.engine.executor.layout_cache
    run_layout_query(db, ALIGNED_SQL)
    assert lc.bytes > 0
    for k in [k for k in db.cache._lru if k[1:2] == ("grid",)]:
        db.cache._evict(k)
    assert len(lc) == 0 and lc.bytes == 0
    # next query rebuilds both and still pins parity
    run_layout_query(db, ALIGNED_SQL)


def test_drop_table_frees_the_layout(db, run_layout_query):
    lc = db.engine.executor.layout_cache
    run_layout_query(db, ALIGNED_SQL)
    assert lc.bytes > 0
    # DROP chains through RegionCacheManager.invalidate_region: the dead
    # region's partials must free immediately, not linger as phantom
    # workload usage until LRU pressure
    db.sql("DROP TABLE cpu")
    assert len(lc) == 0 and lc.bytes == 0


def test_explain_analyze_reports_layout(db):
    db.sql(ALIGNED_SQL)
    res = db.sql("EXPLAIN ANALYZE " + ALIGNED_SQL)
    txt = res.rows[1][1]
    assert "layout: bucket_major" in txt
    assert "layout_cache: hit" in txt
    un = ALIGNED_SQL.replace(str(ALIGNED_LO), str(ALIGNED_LO + 7000))
    txt2 = db.sql("EXPLAIN ANALYZE " + un).rows[1][1]
    assert "layout: dynamic_slice" in txt2
