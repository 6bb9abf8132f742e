"""Dense time-grid executor: equivalence vs the row-oriented path.

Every query here runs twice — the grid path, and the row DeviceTable path
(conftest's ``ineligible("grid")``) — on the same data; results must
agree.  The row path is itself golden-tested, so agreement pins the grid
kernels.
"""

import numpy as np
import pytest

from greptimedb_tpu.query.physical import DISPATCH_STATS
from greptimedb_tpu.standalone import GreptimeDB


def _rows(res):
    return sorted(
        res.rows, key=lambda r: tuple("" if v is None else str(v) for v in r)
    )


def _assert_rows_close(a, b, sql):
    assert len(a) == len(b), f"{len(a)} vs {len(b)} rows: {sql}"
    for ra, rb in zip(a, b):
        assert len(ra) == len(rb), sql
        for va, vb in zip(ra, rb):
            if isinstance(va, float) or isinstance(vb, float):
                # f32 accumulation order differs between the reshape
                # reduction and the scatter reduction
                assert va == pytest.approx(vb, rel=2e-5, abs=1e-5), (
                    f"{va} vs {vb}: {sql}")
            else:
                assert va == vb, f"{va} vs {vb}: {sql}"


@pytest.fixture
def run_both(ineligible):
    def run_both(db, sql, expect_grid=True):
        before = DISPATCH_STATS["grid"]
        r_grid = db.sql(sql)
        used = DISPATCH_STATS["grid"] > before
        assert used == expect_grid, (
            f"grid used={used}, expected {expect_grid}: {sql}"
        )
        before = DISPATCH_STATS["grid"]
        with ineligible("grid"):
            r_row = db.sql(sql)
        assert DISPATCH_STATS["grid"] == before, "reference ran the grid"
        assert r_grid.column_names == r_row.column_names, sql
        _assert_rows_close(_rows(r_grid), _rows(r_row), sql)
        return r_grid

    return run_both


@pytest.fixture
def db(tmp_path):
    d = GreptimeDB(str(tmp_path / "g"))
    d.sql(
        "CREATE TABLE cpu (host STRING, dc STRING, "
        "ts TIMESTAMP(3) TIME INDEX, usage DOUBLE, mem DOUBLE, "
        "PRIMARY KEY (host, dc))"
    )
    rng = np.random.default_rng(3)
    rows = []
    t0 = 1700000000000
    for k in range(240):  # 240 steps @ 5s for 6 hosts: regular cadence
        for h in range(6):
            u = round(float(rng.uniform(0, 100)), 3)
            m = "NULL" if (k * 6 + h) % 17 == 0 else round(
                float(rng.uniform(0, 64)), 3)
            rows.append(
                f"('h{h}','dc{h % 2}',{t0 + k * 5000},{u},{m})"
            )
    d.sql("INSERT INTO cpu VALUES " + ",".join(rows))
    d._region_of("cpu").flush()
    yield d
    d.close()


def test_double_groupby(db, run_both):
    r = run_both(db, "SELECT host, date_trunc('minute', ts) AS m, "
                     "avg(usage), avg(mem) FROM cpu GROUP BY host, m")
    # 240 steps @5s = 1200s spanning 21 partial minutes (t0 not aligned)
    assert r.num_rows == 6 * 21


def test_key_order_time_first(db, run_both):
    run_both(db, "SELECT date_trunc('minute', ts) AS m, host, avg(usage) "
                 "FROM cpu GROUP BY m, host")


def test_all_ops(db, run_both):
    run_both(db, "SELECT dc, count(*), count(mem), sum(usage), min(mem), "
                 "max(usage), avg(mem) FROM cpu GROUP BY dc")


def test_global_agg(db, run_both):
    r = run_both(db, "SELECT count(*), avg(usage) FROM cpu")
    assert r.num_rows == 1


def test_global_agg_empty_window(db, run_both):
    r = run_both(db, "SELECT count(*), max(usage) FROM cpu WHERE ts < 5")
    assert r.rows[0][0] == 0 and r.rows[0][1] is None


def test_time_window_and_tag_filter(db, run_both):
    run_both(db, "SELECT host, date_trunc('minute', ts) AS m, avg(usage) "
                 "FROM cpu WHERE ts >= 1700000300000 AND ts < 1700000900000 "
                 "AND dc = 'dc0' GROUP BY host, m")


def test_field_predicate(db, run_both):
    run_both(db, "SELECT host, count(*) FROM cpu WHERE usage > 50 "
                 "GROUP BY host")


def test_expression_agg(db, run_both):
    run_both(db, "SELECT host, avg(usage + mem), sum(usage * 2) "
                 "FROM cpu GROUP BY host")


def test_unaligned_window_start(db, run_both):
    # window start not aligned to the minute buckets nor the 5s grid
    run_both(db, "SELECT date_trunc('minute', ts) AS m, sum(usage) "
                 "FROM cpu WHERE ts >= 1700000302000 GROUP BY m")


def test_delete_excluded(db, run_both):
    db.sql("DELETE FROM cpu WHERE host = 'h1' AND dc = 'dc1' "
           "AND ts = 1700000000000")
    run_both(db, "SELECT host, count(*) FROM cpu GROUP BY host")


def test_append_extension(db, run_both):
    # first query builds the grid; appends then extend it device-side
    run_both(db, "SELECT host, count(*) FROM cpu GROUP BY host")
    t = 1700000000000 + 240 * 5000
    db.sql(f"INSERT INTO cpu VALUES ('h0','dc0',{t},50.0,32.0),"
           f"('h6','dc0',{t},60.0,16.0)")  # h6 = new series
    r = run_both(db, "SELECT host, count(*) FROM cpu GROUP BY host")
    counts = dict((row[0], row[1]) for row in r.rows)
    assert counts["h6"] == 1 and counts["h0"] == 241


def test_irregular_falls_back(tmp_path, run_both):
    db = GreptimeDB(str(tmp_path / "i"))
    db.sql("CREATE TABLE ev (h STRING, ts TIMESTAMP(3) TIME INDEX, "
           "v DOUBLE, PRIMARY KEY (h))")
    rng = np.random.default_rng(5)
    t = 1700000000000
    vals = []
    for _ in range(500):
        t += int(rng.integers(1, 50000))  # ragged millisecond gaps
        vals.append(f"('x',{t},{float(rng.uniform())})")
    db.sql("INSERT INTO ev VALUES " + ",".join(vals))
    db._region_of("ev").flush()
    run_both(db, "SELECT h, count(*), avg(v) FROM ev GROUP BY h",
             expect_grid=False)
    db.close()


def test_unsupported_aggs_fall_back(db, run_both):
    run_both(db, "SELECT host, count(DISTINCT dc) FROM cpu GROUP BY host",
             expect_grid=False)
    run_both(db, "SELECT host, stddev(usage) FROM cpu GROUP BY host",
             expect_grid=False)


def test_grid_vs_row_after_flush_cycles(db, run_both):
    # second flush (structure change) → grid rebuild on next query
    t = 1700000000000 + 300 * 5000
    db.sql(f"INSERT INTO cpu VALUES ('h2','dc0',{t},10.0,1.0)")
    db._region_of("cpu").flush()
    run_both(db, "SELECT host, max(usage) FROM cpu GROUP BY host")


def test_delete_with_default_fill_excluded_from_sums(tmp_path, run_both):
    # tombstone rows carry schema DEFAULT fills in their field payload;
    # the mask-free sum fast path must not count them (review r4 finding)
    db = GreptimeDB(str(tmp_path / "d"))
    db.sql("CREATE TABLE m (h STRING, ts TIMESTAMP(3) TIME INDEX, "
           "v DOUBLE DEFAULT 2.0, PRIMARY KEY (h))")
    t0 = 1700000000000
    db.sql("INSERT INTO m VALUES " + ",".join(
        f"('a',{t0 + k * 1000},10.0)" for k in range(50)))
    db.sql(f"DELETE FROM m WHERE h = 'a' AND ts = {t0 + 10 * 1000}")
    db._region_of("m").flush()
    r = run_both(db, "SELECT h, sum(v), avg(v), count(v) FROM m GROUP BY h")
    assert r.rows == [["a", 490.0, 10.0, 49]]
    db.close()


def test_inf_values_take_masked_path(tmp_path, run_both):
    # written ±inf must not meet the 0/1 weight multiply (inf*0 = NaN)
    db = GreptimeDB(str(tmp_path / "inf"))
    db.sql("CREATE TABLE m (h STRING, ts TIMESTAMP(3) TIME INDEX, "
           "v DOUBLE, PRIMARY KEY (h))")
    t0 = 1700000000000
    vals = [f"('a',{t0 + k * 1000},1.0)" for k in range(50)]
    vals[5] = f"('a',{t0 + 5000},1e39)"  # overflows f32 → inf in the grid
    db.sql("INSERT INTO m VALUES " + ",".join(vals))
    db._region_of("m").flush()
    # window excludes the inf row: sums over [t0+10s, t0+50s) stay finite
    r = run_both(
        db,
        f"SELECT h, sum(v), count(v) FROM m "
        f"WHERE ts >= {t0 + 10000} AND ts < {t0 + 50000} GROUP BY h",
    )
    assert r.rows == [["a", 40.0, 40]]
    # window including it yields inf (matches the row path semantics)
    r2 = run_both(db, "SELECT h, sum(v) FROM m GROUP BY h")
    assert r2.rows[0][1] == float("inf")
    db.close()


def test_grid_snapshot_roundtrip(db, tmp_path, run_both):
    # snapshot persist/restore: same tensors, installed as the live entry
    from greptimedb_tpu.storage.grid import (
        load_grid_snapshot, save_grid_snapshot,
    )

    region = db._table_view("cpu")
    table, _ = db.grid_table("cpu", None)
    assert table is not None
    snap = str(tmp_path / "snap")
    save_grid_snapshot(table, region, snap)
    restored = load_grid_snapshot(snap, region)
    assert restored is not None
    np.testing.assert_array_equal(
        np.asarray(restored.values), np.asarray(table.values))
    np.testing.assert_array_equal(
        np.asarray(restored.valid), np.asarray(table.valid))
    assert restored.dicts == table.dicts
    assert restored.no_nan == table.no_nan
    db.cache.install_grid(region, restored)
    r = run_both(db, "SELECT host, avg(usage), count(*) FROM cpu GROUP BY host")
    assert r.num_rows == 6
    # mutate the region: fingerprint mismatch → restore refuses
    t = 1700000000000 + 400 * 5000
    db.sql(f"INSERT INTO cpu VALUES ('h0','dc0',{t},1.0,1.0)")
    db._region_of("cpu").flush()
    assert load_grid_snapshot(snap, region) is None
