"""Resident PromQL evaluation cache (promql/engine.py + PromLayoutCache).

The cache must be invisible except for speed: every parity test pins the
cached (warm, resident selection/sort/group state) evaluation BIT-EXACT
against an evaluator on a db without ``promql_cache`` — both serve from the identical
transient-build code path, so equality is structural, not tolerance-based.
Invalidation tests prove the generation discipline: data appends rebuild
the resident sort layout (dicts_version), registry growth rebuilds the
selection and group-id state (series_generation).  The guard test pins
label materialization to O(output groups) so the round-5 O(series) host
loop cannot silently regress.
"""


import numpy as np
import pytest

from greptimedb_tpu.promql.engine import (
    LazySeriesLabels, PromEvaluator,
)
from greptimedb_tpu.promql.parser import parse_promql
from greptimedb_tpu.standalone import GreptimeDB


@pytest.fixture
def db():
    d = GreptimeDB()
    yield d
    d.close()


def seed_counters(db, name="m", pods=4, containers=3, n=40, step_s=10):
    db.sql(
        f"CREATE TABLE {name} (pod STRING, container STRING, "
        f"ts TIMESTAMP(3) TIME INDEX, val DOUBLE, PRIMARY KEY "
        f"(pod, container))"
    )
    r = db._region_of(name)
    ts = np.arange(n) * step_s * 1000
    rng = np.random.default_rng(7)
    for p in range(pods):
        for c in range(containers):
            base = rng.uniform(1.0, 9.0)
            vals = np.cumsum(rng.uniform(0, 2 * base, n))
            if (p + c) % 3 == 0:  # sprinkle counter resets
                vals[n // 2:] -= vals[n // 2]
            r.write({
                "pod": [f"p{p}"] * n, "container": [f"c{c}"] * n,
                "ts": ts, "val": vals,
            })
    return r


def eval_q(db, query, start=300, end=300, step=60):
    ev = PromEvaluator(db, start, end, step)
    res = ev.eval(parse_promql(query))
    return np.asarray(res.values), list(res.labels), ev


class _NoPromCache:
    """The db as a frontend handle shows it: everything but the resident
    ``promql_cache``, so the evaluator builds its state transiently."""

    def __init__(self, db):
        self._db = db

    def __getattr__(self, name):
        if name == "promql_cache":
            raise AttributeError(name)
        return getattr(self._db, name)


def eval_uncached(db, query, **kw):
    return eval_q(_NoPromCache(db), query, **kw)


PARITY_QUERIES = [
    'rate(m[5m])',
    'increase(m[5m])',
    'irate(m[2m])',
    'delta(m[5m])',
    'changes(m[5m])',
    'sum by (pod) (rate(m[5m]))',
    'sum without (container) (rate(m[5m]))',
    'avg by (pod) (rate(m[5m]))',
    'count by (container) (rate(m[5m]))',
    'quantile(0.5, rate(m[5m]))',
    'quantile by (pod) (0.9, rate(m[5m]))',
    'topk(3, rate(m[5m]))',
    'topk by (pod) (2, rate(m[5m]))',
    'bottomk by (container) (1, rate(m[5m]))',
    'min_over_time(m[3m])',
    'quantile_over_time(0.5, m[3m])',
    'sum by (pod) (rate(m{pod=~"p[02]"}[5m]))',
    'stddev by (pod) (m)',
]


class TestCachedUncachedParity:
    def test_bit_exact_parity(self, db):
        seed_counters(db)
        for q in PARITY_QUERIES:
            cold_v, cold_l, _ = eval_q(db, q)  # populates the caches
            warm_v, warm_l, ev = eval_q(db, q)  # served resident
            off_v, off_l, _ = eval_uncached(db, q)
            assert np.array_equal(warm_v, off_v, equal_nan=True), q
            assert np.array_equal(cold_v, off_v, equal_nan=True), q
            assert warm_l == off_l and cold_l == off_l, q

    def test_range_query_parity(self, db):
        seed_counters(db)
        q = 'sum by (pod) (rate(m[2m]))'
        eval_q(db, q, start=120, end=360, step=30)
        warm_v, warm_l, _ = eval_q(db, q, start=120, end=360, step=30)
        off_v, off_l, _ = eval_uncached(db, q, start=120, end=360, step=30)
        assert warm_v.shape == off_v.shape and warm_v.shape[1] == 9
        assert np.array_equal(warm_v, off_v, equal_nan=True)
        assert warm_l == off_l

    def test_warm_eval_hits_all_caches(self, db):
        seed_counters(db)
        q = 'sum by (pod) (rate(m[5m]))'
        eval_q(db, q)
        _, _, ev = eval_q(db, q)
        assert ev.cache_events["selection_hit"] >= 1
        assert ev.cache_events["sort_hit"] >= 1
        assert ev.cache_events["group_hit"] >= 1
        assert ev.cache_events.get("sort_miss", 0) == 0

    def test_unknown_metric_first_selector(self, db):
        # seed bug: rate() over an unknown metric as the evaluator's FIRST
        # selector crashed on the unset window grid instead of returning
        # an empty vector
        v, l, _ = eval_q(db, 'rate(nosuch[5m])')
        assert v.shape[0] == 0 and l == []
        v, l, _ = eval_q(db, 'sum by (pod) (rate(nosuch[5m]))')
        assert v.shape[0] == 0

    def test_label_transform_falls_back_to_host_grouping(self, db):
        seed_counters(db)
        q = ('sum by (dst) (label_replace(rate(m[5m]), "dst", "$1", '
             '"pod", "(p.)"))')
        v1, l1, _ = eval_q(db, q)
        v2, l2, _ = eval_uncached(db, q)
        assert np.array_equal(v1, v2, equal_nan=True)
        assert l1 == l2 and len(l1) == 4


class TestInvalidation:
    def test_data_append_rebuilds_sort_layout(self, db):
        r = seed_counters(db, n=30)
        q = 'sum by (pod) (increase(m[5m]))'
        eval_q(db, q)
        eval_q(db, q)
        misses_before = db.promql_cache.misses["sort"]
        sel_misses_before = db.promql_cache.misses["selection"]
        # append NEW samples for EXISTING series: the resident sort is
        # stale (dicts_version bump), the selection is not (registry
        # unchanged)
        ts = (np.arange(5) + 30) * 10_000
        r.write({"pod": ["p0"] * 5, "container": ["c0"] * 5, "ts": ts,
                 "val": np.linspace(1e6, 2e6, 5)})
        on_v, on_l, ev = eval_q(db, q)
        off_v, off_l, _ = eval_uncached(db, q)
        assert np.array_equal(on_v, off_v, equal_nan=True)
        assert on_l == off_l
        assert db.promql_cache.misses["sort"] > misses_before
        assert db.promql_cache.misses["selection"] == sel_misses_before
        # the appended 1e6-scale jump must be visible in p0's increase
        p0 = on_l.index({"pod": "p0"})
        assert float(on_v[p0, 0]) > 1e5

    def test_new_series_rebuilds_selection_and_groups(self, db):
        r = seed_counters(db, pods=2, containers=2, n=20)
        q = 'sum by (pod) (rate(m[5m]))'
        v1, l1, _ = eval_q(db, q, start=200, end=200)
        assert len(l1) == 2
        sel_misses = db.promql_cache.misses["selection"]
        grp_misses = db.promql_cache.misses["group"]
        ts = np.arange(20) * 10_000
        r.write({"pod": ["p9"] * 20, "container": ["c0"] * 20, "ts": ts,
                 "val": np.cumsum(np.full(20, 3.0))})
        v2, l2, ev = eval_q(db, q, start=200, end=200)
        off_v, off_l, _ = eval_uncached(db, q, start=200, end=200)
        assert len(l2) == 3 and {"pod": "p9"} in l2
        assert np.array_equal(v2, off_v, equal_nan=True)
        assert l2 == off_l
        assert db.promql_cache.misses["selection"] > sel_misses
        assert db.promql_cache.misses["group"] > grp_misses

    def test_invalidate_region_drops_entries(self, db):
        seed_counters(db)
        eval_q(db, 'sum by (pod) (rate(m[5m]))')
        assert len(db.promql_cache) > 0
        db.sql("DROP TABLE m")
        assert len(db.promql_cache) == 0

    def test_stats_shape(self, db):
        seed_counters(db)
        eval_q(db, 'rate(m[5m])')
        s = db.promql_cache.stats()
        for k in ("bytes", "entries", "rejects", "builds", "selection_hits",
                  "sort_misses", "group_hits"):
            assert k in s


class TestQuotaRejectToFallback:
    def test_rejected_build_serves_uncached(self, db):
        seed_counters(db)
        db.memory.set_quota("promql_cache", 1)  # nothing can admit
        v1, l1, ev = eval_q(db, 'sum by (pod) (rate(m[5m]))')
        off_v, off_l, _ = eval_uncached(db, 'sum by (pod) (rate(m[5m]))')
        assert np.array_equal(v1, off_v, equal_nan=True)
        assert l1 == off_l
        assert db.promql_cache.rejects > 0
        assert len(db.promql_cache) == 0
        assert db.memory.usage()["promql_cache"]["rejected"] > 0
        db.memory.set_quota("promql_cache", None)
        eval_q(db, 'sum by (pod) (rate(m[5m]))')
        assert len(db.promql_cache) > 0


class TestMeshSharding:
    def test_resident_sort_layout_is_series_sharded(self, db):
        import jax

        if db.cache.mesh is None or len(jax.devices()) < 2:
            pytest.skip("needs the 8-device virtual mesh")
        seed_counters(db)
        eval_q(db, 'sum by (pod) (rate(m[5m]))')
        v_on, l_on, _ = eval_q(db, 'sum by (pod) (rate(m[5m]))')
        entry = [k for k in db.promql_cache._lru if k[1] == "sort"]
        assert entry, "sort layout not resident"
        layout = db.promql_cache._lru[entry[0]].arrays[0]
        key_s = layout.val_s
        ndev = len(set(key_s.sharding.device_set))
        assert ndev == db.cache.mesh.devices.size, key_s.sharding
        # sharded placement must not change results
        off_v, off_l, _ = eval_uncached(db, 'sum by (pod) (rate(m[5m]))')
        assert np.array_equal(v_on, off_v, equal_nan=True)


class TestLabelMaterializationGuard:
    """Tier-1 guard: a 50k-series aggregation must decode O(output
    groups) label dicts, not O(series) — the LazySeriesLabels
    materialization counter is the dict-construction probe."""

    SERIES = 50_000
    PODS = 5_000

    def test_aggregation_is_o_groups(self, db):
        db.sql(
            "CREATE TABLE big (pod STRING, container STRING, "
            "ts TIMESTAMP(3) TIME INDEX, val DOUBLE, PRIMARY KEY "
            "(pod, container))"
        )
        r = db._region_of("big")
        per_pod = self.SERIES // self.PODS
        pods = np.array([f"pod-{i}" for i in range(self.PODS)], dtype=object)
        conts = np.array([f"c{i}" for i in range(per_pod)], dtype=object)
        pod_col = pods[np.arange(self.SERIES) // per_pod]
        cont_col = conts[np.arange(self.SERIES) % per_pod]
        rng = np.random.default_rng(3)
        counters = rng.uniform(0, 100, self.SERIES)
        for k in range(2):
            counters = counters + rng.uniform(10, 20, self.SERIES)
            r.write({
                "pod": pod_col, "container": cont_col,
                "ts": np.full(self.SERIES, k * 15_000, dtype=np.int64),
                "val": counters,
            })
        expr = parse_promql('sum by (pod) (rate(big[5m]))')
        ev = PromEvaluator(db, 15, 15, 1.0)
        LazySeriesLabels.materializations = 0
        res = ev.eval(expr)
        np.asarray(res.values)  # force values
        assert res.num_series == self.PODS
        # evaluation itself (selection, window kernel, grouping) must not
        # build ANY per-series label dict
        assert LazySeriesLabels.materializations == 0
        # decoding every output group costs exactly one source-series
        # materialization per group
        labels = list(res.labels)
        assert len(labels) == self.PODS
        assert labels[0] == {"pod": "pod-0"}
        assert LazySeriesLabels.materializations <= self.PODS
