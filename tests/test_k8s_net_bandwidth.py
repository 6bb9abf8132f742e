"""The cell ``k8snet120k.namespace_bandwidth`` at its rehearsal size on the
CPU: cAdvisor's network byte counters (benchmark/datasets/
k8s_cadvisor_net.py: two tables, restarts, replaced pods, counters up to
3 days old) loaded through the HTTP server, the cluster page's two
bandwidth panels sent through ``/v1/prometheus/api/v1/query_range``,
keys compared exactly and values with the family's own reference over
the **float64** samples (benchmark/queries/k8s_net_rate.py) under the
family's limits.  What the deployment forced of the program is held here
too: a stored DOUBLE counter's increase is exact on the device — through
a reset, an extend of the resident table and a flush — on a layout that
is wide because the column is (no option), and a column of small
magnitudes keeps the narrow layout and the program it had.
"""

import json
import urllib.parse
import urllib.request

import numpy as np
import pytest

from greptimedb_tpu.promql import engine as pe
from greptimedb_tpu.servers import HttpServer
from greptimedb_tpu.standalone import GreptimeDB
from greptimedb_tpu.storage.cache import low_word_col
from greptimedb_tpu.utils.telemetry import REGISTRY

WORKLOAD = "k8snet120k.namespace_bandwidth"
ROWS = "greptime_promql_window_rows_total"
WIDE = "greptime_promql_wide_rows_total"


@pytest.mark.parametrize(
    "served", [(WORKLOAD, 7), (WORKLOAD, 2100000777, "one-device"),
               (WORKLOAD, 1900000333, "one-device")], indirect=True)
def test_replies_agree_with_the_float64_reference(served):
    ds, family = served.cell.ds, served.family
    for table in ds.TABLES:
        vals = served.cell.data["values"][table]
        # the data holds what the issue asks the program to cope with
        assert (np.diff(vals, axis=0) < 0).any()       # a restart
        assert np.isnan(vals[0]).any()                 # a new pod
        assert np.isnan(vals[-1]).any()                # a replaced one
        assert np.nanmax(vals) > 2.0 ** 36             # far past f32's 2^24
        assert np.nanmax(vals) < ds.LIMIT
        assert (vals[~np.isnan(vals)] % 1 == 0).all()  # whole bytes
    seen = set()
    for _ in range(4):
        req = served.traffic.next()
        seen.add(req["class"])
        limit = family.LIMITS[req["class"]]
        verdict, err = served.judge(req)
        assert verdict == "ok"
        assert err <= limit / 3
        keys, _vals = family.reference(served.cell, req)
        assert len(keys) == served.cell.params["namespaces"] * 61
        # the control: the same reference over samples a float32 value
        # column would hold lies over the limit, on every request
        verdict, err = served.judge(
            req, answer=family.reference(served.cell, req, family.F32))
        assert verdict == "ok"
        assert err > 1.5 * limit
    assert seen == set(family.LIMITS)


@pytest.mark.parametrize("served", [(WORKLOAD, 11)], indirect=True)
def test_wide_rows_are_counted_and_read(served):
    rows, wide = REGISTRY.value(ROWS, ()), REGISTRY.value(WIDE, ())
    reqs = [served.traffic.next() for _ in range(2)]
    assert {r["class"] for r in reqs} == set(served.family.LIMITS)
    for req in reqs:
        assert served.judge(req)[0] == "ok"
    # 84 series a table pad to 128; 35 min at 30 s give W = 128 (the cap
    # of a 120-sample run): both dispatches ran on a wide layout
    assert REGISTRY.value(ROWS, ()) - rows == 2 * 128 * 128
    assert REGISTRY.value(WIDE, ()) - wide == 2 * 128 * 128
    for table in served.cell.ds.TABLES:
        cols = served.db.cache.get(served.db._region_of(table)).columns
        assert low_word_col("greptime_value") in cols
    after = served.client.metrics()
    assert WIDE in after
    read = served.run.load_module("layer_metrics", "wide_value_pct").read
    assert read({"metrics_before": {ROWS: rows, WIDE: wide},
                 "metrics_after": after}) == 100.0
    # a program without the counter gives nothing and does not raise
    assert read({"metrics_before": {}, "metrics_after": {ROWS: 5.0}}) is None
    assert read({"metrics_before": {}, "metrics_after": {"x": 1.0}}) is None


@pytest.mark.parametrize("served", [("k8s100k.namespace_cpu", 7)],
                         indirect=True)
def test_small_magnitudes_keep_the_narrow_layout(served):
    """CPU seconds stay under 2^24: no low word, the narrow layout, the
    program key of before, and not one wide row."""
    def fused(wide: bool):
        return {k for k in pe._KERNEL_CACHE if isinstance(k, tuple)
                and k[0] == "promql_fused" and k[1].wide is wide}

    wide_rows, wide_programs = REGISTRY.value(WIDE, ()), fused(True)
    for _ in range(2):
        assert served.judge(served.traffic.next())[0] == "ok"
    assert REGISTRY.value(WIDE, ()) == wide_rows
    assert fused(True) == wide_programs      # none built, none asked for
    table = served.cell.ds.TABLE
    cols = served.db.cache.get(served.db._region_of(table)).columns
    assert not any(name.startswith("__lo_") for name in cols)
    # the key but for the new field is the one the narrow program had
    narrow = fused(False)
    assert narrow and all(repr(k[1]).endswith(", wide=False)")
                          for k in narrow)


def _points(port: int, query: str, start: int, end: int, step: int):
    qs = urllib.parse.urlencode({"query": query, "start": start, "end": end,
                                 "step": step})
    url = f"http://127.0.0.1:{port}/v1/prometheus/api/v1/query_range?{qs}"
    with urllib.request.urlopen(url) as r:
        body = json.loads(r.read())
    assert body["status"] == "success"
    return {s["metric"].get("pod", ""): {int(float(t)): float(v)
                                         for t, v in s["values"]}
            for s in body["data"]["result"]}


def _insert(db, **samples):
    """One body: an extend needs every row of it later than the table's."""
    db.sql("INSERT INTO bytes_total VALUES " + ",".join(
        f"('{pod}', {1000 * t}, {v!r})"
        for pod, rows in samples.items() for t, v in rows))


def test_counter_at_2_45_rises_by_one_exactly():
    """A counter at 2^45 that rises by 1 a scrape: float32 holds it in
    steps of 4,194,304, so its increase over any window reads 0 there.
    Exact here, across a reset, across a second body written after the
    first query (the resident table is extended) and after a flush."""
    base = float(1 << 45)
    db = GreptimeDB()
    srv = HttpServer(db, port=0)
    srv.start()
    try:
        db.sql("CREATE TABLE bytes_total (pod STRING, ts TIMESTAMP(3) TIME "
               "INDEX, greptime_value DOUBLE, PRIMARY KEY (pod))")
        # 'a' rises by 1 every 30 s; 'b' by 3 and falls to 2 at scrape 12
        a = [(30 * i, base + i) for i in range(40)]
        b = [(30 * i, base + 3 * i if i < 12 else 2.0 + 3 * (i - 12))
             for i in range(40)]
        _insert(db, a=a[:20], b=b[:20])
        inc = "sum by (pod)(increase(bytes_total[2m]))"
        rate = "sum by (pod)(rate(bytes_total[2m]))"

        def near(x):
            return pytest.approx(x, rel=1e-6)

        def check(end_i):
            """Windows that end on a scrape hold 4 samples 90 s apart,
            extrapolated to 120 s: increase = 4/3 of the difference."""
            t = 30 * end_i
            got = _points(srv.port, inc, t - 60, t, 30)
            rates = _points(srv.port, rate, t - 60, t, 30)
            for j in (end_i - 2, end_i - 1, end_i):
                assert got["a"][30 * j] == near(3 * 4 / 3)
                assert rates["a"][30 * j] == near(3 * 4 / 3 / 120)
                assert got["b"][30 * j] == near(9 * 4 / 3)

        check(19)
        region = db._region_of("bytes_total")
        table = db.cache.get(region)
        assert low_word_col("greptime_value") in table.columns
        extends = db.cache.extends
        # b's fall at scrape 12: what fell is added back, so the windows
        # it lies in read 8 (the 2 it restarted at in place of a step of 3)
        fall = _points(srv.port, inc, 30 * 11, 30 * 16, 30)
        assert [fall["b"][30 * j] for j in (11, 12, 13, 14, 16)] == [
            near(9 * 4 / 3)] + [near(8 * 4 / 3)] * 3 + [near(9 * 4 / 3)]
        # and read through irate and resets on the wide pair
        fall = _points(srv.port, "sum by (pod)(irate(bytes_total[2m]))",
                       30 * 11, 30 * 13, 30)
        assert fall["a"][30 * 12] == near(1 / 30)
        assert fall["b"][30 * 11] == near(3 / 30)
        assert fall["b"][30 * 12] == near(2 / 30)   # the new value
        resets = _points(srv.port, "sum by (pod)(resets(bytes_total[5m]))",
                         30 * 15, 30 * 15, 30)
        assert resets["b"][30 * 15] == 1.0 and resets["a"][30 * 15] == 0.0
        # a second body after the first query: the table is extended
        _insert(db, a=a[20:30], b=b[20:30])
        check(29)
        assert db.cache.extends > extends
        seam = _points(srv.port, inc, 30 * 19, 30 * 22, 30)   # across it
        for j in (19, 20, 21, 22):
            assert seam["a"][30 * j] == near(3 * 4 / 3)
        # and after a flush
        db.sql("ADMIN flush_table('bytes_total')")
        _insert(db, a=a[30:], b=b[30:])
        check(39)
        seam = _points(srv.port, inc, 30 * 28, 30 * 32, 30)
        for j in range(28, 33):
            assert seam["a"][30 * j] == near(3 * 4 / 3)
            assert seam["b"][30 * j] == near(9 * 4 / 3)
    finally:
        srv.stop()
        db.close()


def test_a_delta_that_first_passes_2_24_rebuilds_the_table():
    """The resident table of a column under 2^24 has no low word; a
    later body that passes 2^24 cannot be extended into it: the table is
    built anew, wide, and the increase across the seam is exact."""
    db = GreptimeDB()
    srv = HttpServer(db, port=0)
    srv.start()
    try:
        db.sql("CREATE TABLE bytes_total (pod STRING, ts TIMESTAMP(3) TIME "
               "INDEX, greptime_value DOUBLE, PRIMARY KEY (pod))")
        top = float(1 << 24)
        samples = [(30 * i, top - 10 + i) for i in range(30)]
        _insert(db, a=samples[:8])        # all under 2^24
        q = "sum by (pod)(increase(bytes_total[2m]))"
        first = _points(srv.port, q, 30 * 5, 30 * 7, 30)
        assert first["a"][30 * 7] == pytest.approx(3 * 4 / 3, rel=1e-6)
        region = db._region_of("bytes_total")
        assert low_word_col("greptime_value") not in \
            db.cache.get(region).columns
        _insert(db, a=samples[8:])        # 2^24 - 2 ... 2^24 + 19
        got = _points(srv.port, q, 30 * 5, 30 * 29, 30)
        assert low_word_col("greptime_value") in db.cache.get(region).columns
        for j in range(5, 30):
            assert got["a"][30 * j] == pytest.approx(3 * 4 / 3, rel=1e-6)
    finally:
        srv.stop()
        db.close()
