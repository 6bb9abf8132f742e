"""Query-compiler subsystem: fusion parity, persistent cache, warmup.

Covers the PR's acceptance surface:

- whole-plan fusion parity: fused PromQL chains bit-exact vs the
  multi-kernel path (conftest's ``ineligible("fusion")``) across a (function × aggregation op)
  fuzz, and warm SQL grid classes pinned at ONE device dispatch via the
  ``device_dispatches`` counter EXPLAIN ANALYZE surfaces;
- persistent compile cache integrity: corrupt/truncated artifacts
  quarantine and recompile (never a wrong result), stale-environment
  artifacts evict, concurrent processes may share one cache directory;
- AOT warmup: a restarted instance replays its usage journal and serves
  its warm classes with ZERO XLA builds (compile counter pinned 0);
- the where_series stacked-dispatch extension: tag-filtered warm
  windows coalesce into one dispatch, bit-exact vs solo.
"""

import dataclasses
import glob
import json
import os
import pickle
import threading

import numpy as np
import pytest

from greptimedb_tpu.standalone import GreptimeDB
from greptimedb_tpu.utils.telemetry import REGISTRY

T0 = 1451606400000  # TSBS epoch
HOSTS = 4
STEPS = 360  # 1h @ 10s per host


def _fill(db):
    db.sql(
        "CREATE TABLE cpu (h STRING, ts TIMESTAMP(3) TIME INDEX, "
        "v DOUBLE, w DOUBLE, PRIMARY KEY (h))"
    )
    rng = np.random.default_rng(11)
    rows = []
    for hh in range(HOSTS):
        base = rng.uniform(0, 50)
        for i in range(STEPS):
            if rng.random() < 0.03:
                continue  # holes: windows with missing samples
            v = base + i * 0.5 - (200 if i == 180 and hh == 1 else 0)
            w = f"{rng.normal(50, 10)}"
            if rng.random() < 0.02:
                w = "NULL"  # absent samples inside windows
            rows.append(f"('host_{hh}', {T0 + i * 10_000}, {v}, {w})")
    for c in range(0, len(rows), 500):
        db.sql("INSERT INTO cpu VALUES " + ",".join(rows[c:c + 500]))


@pytest.fixture(scope="module")
def db():
    d = GreptimeDB()
    _fill(d)
    yield d
    d.close()


def _window_sql(host: str | None = None) -> str:
    where = f"h = '{host}' AND " if host else ""
    return (
        "SELECT h, date_trunc('hour', ts) AS hour, avg(v), count(v) "
        f"FROM cpu WHERE {where}ts >= {T0} AND ts < {T0 + 3600_000} "
        "GROUP BY h, hour"
    )


# ---------------------------------------------------------------------------
# Shape-class fingerprints
# ---------------------------------------------------------------------------

class TestShape:
    def test_canon_stable_and_discriminating(self):
        from greptimedb_tpu.compile.shape import canon_key, class_id

        key = ('grid_bm', "t=cpu|w=None", 4096, ('v', "w"), 360, 1, 1,
               3_600_000, (4,), (4,), ("h",), False)
        c1 = canon_key('sql', key)
        c2 = canon_key('sql', tuple(key))
        assert c1 == c2 and c1 is not None
        assert class_id(c1) == class_id(c2)
        assert canon_key('sql', key[:-1] + (True,)) != c1
        # numpy scalars normalize through their value, not their repr
        assert canon_key('sql', (np.int64(5),)) == canon_key('sql', (5,))

    def test_unserializable_key_is_anonymous(self):
        from greptimedb_tpu.compile.shape import canon_key

        assert canon_key('sql', (lambda: None,)) is None
        assert canon_key('sql', (1, (2, object()))) is None

    def test_window_params_canonicalize(self):
        from greptimedb_tpu.compile.shape import canon_key
        from greptimedb_tpu.promql.engine import WindowParams

        p = WindowParams(step_ms=60000, num_steps=11, range_ms=300000,
                         num_sel=4, total_series=4, kind="counter",
                         slab_w=64, run_bits=7)
        c = canon_key('promql', (p, "rate", "sum"))
        assert c is not None and "counter" in c and "slab_w=i64" in c
        p2 = dataclasses.replace(p, kind="gauge_window")
        assert canon_key('promql', (p2, "rate", "sum")) != c
        p3 = dataclasses.replace(p, slab_w=128)
        assert canon_key('promql', (p3, "rate", "sum")) != c


# ---------------------------------------------------------------------------
# Envelope + artifact store integrity
# ---------------------------------------------------------------------------

class TestStore:
    def test_envelope_roundtrip_and_corruption(self):
        from greptimedb_tpu.compile.store import (
            decode_envelope, encode_envelope,
        )

        body = b"x" * 1000
        data = encode_envelope(body)
        assert decode_envelope(data) == body
        flipped = bytearray(data)
        flipped[len(data) // 2] ^= 0x40
        assert decode_envelope(bytes(flipped)) is None
        assert decode_envelope(data[:-3]) is None  # truncated
        assert decode_envelope(b"WRONG" + data[5:]) is None

    def _store_with_artifact(self, tmp_path):
        import jax
        import jax.numpy as jnp

        from greptimedb_tpu.compile.store import ArtifactStore

        store = ArtifactStore(str(tmp_path / "cc"))
        compiled = jax.jit(lambda x: (x * 2).sum()).lower(
            jnp.ones((8,), jnp.float32)).compile()
        assert store.save("c" * 24, "canon", "sql", compiled)
        return store

    def test_save_load_roundtrip(self, tmp_path):
        import jax.numpy as jnp

        store = self._store_with_artifact(tmp_path)
        fn = store.load("c" * 24, "canon")
        assert fn is not None
        assert float(fn(jnp.ones((8,), jnp.float32))) == 16.0
        assert store.bytes() > 0

    def test_one_of_eight_devices_loads_on_that_device(self, tmp_path):
        """A program compiled for ONE of the eight virtual devices comes
        back from the store on that device and runs — no rebuild behind
        the scenes (the fallback counter stays where it was)."""
        import jax
        import jax.numpy as jnp

        from greptimedb_tpu.compile.service import PlanCompiler

        dev = jax.devices()[3]
        x = jax.device_put(jnp.arange(8, dtype=jnp.float32), dev)
        key = ("one_device_roundtrip", 8)

        def kernel_of(compiler):
            compiler.configure(str(tmp_path / "cc"))
            return compiler.get_or_build(
                "sql", key, lambda: jax.jit(lambda v: v * 2))

        fallback0 = REGISTRY.value(
            "greptime_compile_cache_events_total", ("fallback",))
        first = PlanCompiler()
        assert kernel_of(first)(x).devices() == {dev}
        assert first.persists == 1
        second = PlanCompiler()
        kern = kernel_of(second)
        assert kern.aot and second.aot_hits == 1
        out = kern(x)
        assert out.devices() == {dev}
        np.testing.assert_array_equal(np.asarray(out), 2 * np.arange(8))
        assert kern.aot, "the artifact was replaced by a rebuild"
        assert REGISTRY.value("greptime_compile_cache_events_total",
                              ("fallback",)) == fallback0

    def test_corrupt_artifact_quarantines(self, tmp_path):
        store = self._store_with_artifact(tmp_path)
        path = glob.glob(os.path.join(store.aot_dir, "*.gtc"))[0]
        with open(path, "r+b") as f:
            f.seek(200)
            b = f.read(1)
            f.seek(200)
            f.write(bytes([b[0] ^ 0xFF]))
        assert store.load("c" * 24) is None
        assert store.corrupt == 1
        assert not os.path.exists(path)  # left the serving dir
        assert glob.glob(os.path.join(store.quarantine_dir, "*"))

    def test_truncated_artifact_quarantines(self, tmp_path):
        store = self._store_with_artifact(tmp_path)
        path = glob.glob(os.path.join(store.aot_dir, "*.gtc"))[0]
        with open(path, "r+b") as f:
            f.truncate(os.path.getsize(path) // 2)
        assert store.load("c" * 24) is None
        assert store.corrupt == 1

    def test_stale_jaxlib_artifact_evicts(self, tmp_path):
        from greptimedb_tpu.compile.store import (
            decode_envelope, encode_envelope,
        )

        store = self._store_with_artifact(tmp_path)
        path = glob.glob(os.path.join(store.aot_dir, "*.gtc"))[0]
        with open(path, "rb") as f:
            doc = pickle.loads(decode_envelope(f.read()))
        doc["env"] = dict(doc["env"], jaxlib="0.0.1")
        with open(path, "wb") as f:
            f.write(encode_envelope(pickle.dumps(doc)))
        assert store.load("c" * 24) is None
        assert store.stale == 1
        assert not os.path.exists(path)  # evicted, not quarantined
        assert not glob.glob(os.path.join(store.quarantine_dir, "*"))

    def test_quota_reclaims_oldest(self, tmp_path):
        import time

        import jax
        import jax.numpy as jnp

        from greptimedb_tpu.compile.store import ArtifactStore

        store = ArtifactStore(str(tmp_path / "cc"))
        compiled = jax.jit(lambda x: x + 1).lower(
            jnp.ones((4,), jnp.float32)).compile()
        for i in range(3):
            assert store.save(f"{i:024d}", None, "sql", compiled)
            ts = time.time() + i  # strictly increasing mtimes
            os.utime(store._path(f"{i:024d}"), (ts, ts))
        total = store.bytes()
        store.quota_bytes = total  # next save must evict the oldest
        assert store.save(f"{3:024d}", None, "sql", compiled)
        assert store.load(f"{0:024d}") is None  # oldest evicted
        assert store.load(f"{3:024d}") is not None

    def test_concurrent_writers_same_dir(self, tmp_path):
        import jax
        import jax.numpy as jnp

        from greptimedb_tpu.compile.store import ArtifactStore

        stores = [ArtifactStore(str(tmp_path / "cc")) for _ in range(2)]
        compiled = jax.jit(lambda x: x * 3).lower(
            jnp.ones((4,), jnp.float32)).compile()
        errs = []

        def worker(s):
            try:
                for _ in range(10):
                    s.save("d" * 24, None, "sql", compiled)
                    s.load("d" * 24)
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        ts = [threading.Thread(target=worker, args=(s,)) for s in stores]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errs
        fn = stores[0].load("d" * 24)
        assert fn is not None
        assert np.allclose(np.asarray(fn(jnp.ones((4,), jnp.float32))), 3.0)


# ---------------------------------------------------------------------------
# Usage journal
# ---------------------------------------------------------------------------

class TestJournal:
    def test_note_top_save_load(self, tmp_path):
        from greptimedb_tpu.compile.journal import UsageJournal

        path = str(tmp_path / "usage.json")
        j = UsageJournal(path)
        for _ in range(3):
            j.note("a" * 24, "sql", "canon_a",
                   lambda: {"kind": "sql_plan", "plan": "{}", "db": "x"})
        j.note("b" * 24, "promql", "canon_b", lambda: None)  # no replay
        j.save()
        j2 = UsageJournal(path)
        assert len(j2) == 2
        top = j2.top(5)
        assert [cid for cid, _e in top] == ["a" * 24]  # replay-less drops
        assert top[0][1]["count"] == 3

    def test_save_merges_concurrent_instances(self, tmp_path):
        from greptimedb_tpu.compile.journal import UsageJournal

        path = str(tmp_path / "usage.json")
        a = UsageJournal(path)
        b = UsageJournal(path)  # second instance sharing the dir
        a.note("a" * 24, "sql", None,
               lambda: {"kind": "tql", "query": "x", "start": 0, "end": 1,
                        "step": 1})
        a.save()
        b.note("b" * 24, "sql", None,
               lambda: {"kind": "tql", "query": "y", "start": 0, "end": 1,
                        "step": 1})
        b.save()  # merge-on-save: must not erase a's class
        j = UsageJournal(path)
        assert len(j) == 2

    def test_drop_replay_tombstone_survives_stale_save(self, tmp_path):
        from greptimedb_tpu.compile.journal import UsageJournal

        path = str(tmp_path / "usage.json")
        rep = {"kind": "tql", "query": "dead", "start": 0, "end": 1,
               "step": 1}
        j = UsageJournal(path)
        j.note("d" * 24, "promql", None, lambda: dict(rep))
        j.save()
        stale = UsageJournal(path)  # loaded while the class was live
        j.drop_replay(rep)
        assert UsageJournal(path).top(5) == []
        stale.save()  # a stale instance's merge cannot resurrect it
        assert UsageJournal(path).top(5) == []

    def test_corrupt_journal_quarantines_and_restarts_empty(self, tmp_path):
        from greptimedb_tpu.compile.journal import UsageJournal

        path = str(tmp_path / "usage.json")
        j = UsageJournal(path)
        j.note("a" * 24, "sql", None, lambda: {"kind": "tql", "query": "m",
                                               "start": 0, "end": 1,
                                               "step": 1})
        j.save()
        with open(path, "r+b") as f:
            f.seek(10)
            f.write(b"\xff\xff")
        j2 = UsageJournal(path)
        assert j2.corrupt and len(j2) == 0
        assert os.path.exists(path + ".quarantine")


# ---------------------------------------------------------------------------
# Whole-plan fusion: PromQL chain parity fuzz
# ---------------------------------------------------------------------------

def _tql(expr: str) -> str:
    lo = T0 // 1000
    return f"TQL EVAL ({lo + 600}, {lo + 3000}, 120) {expr}"


# (function template, aggregation clause) pairs rotating every fused op
# and window-kernel kind through the parity check
_FUZZ_CASES = [
    ('rate(cpu{__field__="v"}[5m])', "sum by (h)"),
    ('rate(cpu{__field__="v"}[3m])', "avg"),
    ('increase(cpu{__field__="v"}[5m])', "max by (h)"),
    ('delta(cpu{__field__="v"}[4m])', "min"),
    ('irate(cpu{__field__="v"}[5m])', "sum"),
    ('idelta(cpu{__field__="v"}[5m])', "count by (h)"),
    ('resets(cpu{__field__="v"}[10m])', "sum by (h)"),
    ('changes(cpu{__field__="v"}[10m])', "max"),
    ('avg_over_time(cpu{__field__="v"}[5m])', "max by (h)"),
    ('sum_over_time(cpu{__field__="v"}[5m])', "group by (h)"),
    ('count_over_time(cpu{__field__="v"}[5m])', "sum without (h)"),
    ('last_over_time(cpu{__field__="v"}[5m])', "avg by (h)"),
    ('first_over_time(cpu{__field__="v"}[5m])', "min by (h)"),
    ('stdvar_over_time(cpu{__field__="v"}[5m])', "sum"),
    ('present_over_time(cpu{__field__="v"}[5m])', "count"),
    ('min_over_time(cpu{__field__="v"}[5m])', "min by (h)"),
    ('max_over_time(cpu{__field__="v"}[5m])', "max"),
    ('deriv(cpu{__field__="v"}[10m])', "avg by (h)"),
    ('cpu{__field__="v"}', "sum by (h)"),  # instant selector under the aggregation
    ('cpu{__field__="v"} offset 2m', "avg"),
]


class TestFusionParity:
    @pytest.mark.parametrize('func,agg', _FUZZ_CASES,
                             ids=[f"{a}_{f[:12]}" for f, a in _FUZZ_CASES])
    def test_fused_vs_unfused_bit_exact(self, db, func, agg, ineligible):
        from greptimedb_tpu.compile.fused import FUSED_DISPATCHES

        q = _tql(f"{agg} ({func})")
        before = FUSED_DISPATCHES["count"]
        fused = db.sql(q)
        assert FUSED_DISPATCHES["count"] > before, "fused path not taken"
        before = FUSED_DISPATCHES["count"]
        with ineligible("fusion"):
            plain = db.sql(q)
        assert FUSED_DISPATCHES["count"] == before, "reference ran fused"
        assert fused.column_names == plain.column_names
        # BIT-exact: float cells compare with ==, not approx
        assert fused.rows == plain.rows

    def test_unfusable_shapes_fall_back(self, db):
        from greptimedb_tpu.compile.fused import FUSED_DISPATCHES

        before = FUSED_DISPATCHES["count"]
        # quantile/stddev ops, subquery input: all outside the fused
        # surface — must run (correctly) on the multi-kernel path
        r1 = db.sql(_tql('quantile by (h) (0.9, rate(cpu{__field__="v"}[5m]))'))
        r2 = db.sql(_tql('sum by (h) (avg_over_time(cpu{__field__="v"}[10m:2m]))'))
        r3 = db.sql(_tql('stddev by (h) (rate(cpu{__field__="v"}[5m]))'))
        assert FUSED_DISPATCHES["count"] == before
        assert r1.num_rows > 0 and r2.num_rows > 0 and r3.num_rows > 0

    def test_fused_single_device_dispatch(self, db):
        """The fused chain is ONE kernel dispatch: DISPATCH_STATS'
        timed-call counter must not move (the fused call bypasses the
        SQL dispatch sites entirely), while the fused counter does."""
        from greptimedb_tpu.compile.fused import FUSED_DISPATCHES

        q = _tql('sum by (h) (rate(cpu{__field__="v"}[5m]))')
        db.sql(q)  # warm (compile outside the pinned window)
        before = FUSED_DISPATCHES["count"]
        db.sql(q)
        assert FUSED_DISPATCHES["count"] == before + 1


# ---------------------------------------------------------------------------
# SQL grid path: one dispatch per warm query, EXPLAIN ANALYZE pin
# ---------------------------------------------------------------------------

class TestSqlDispatchPin:
    def test_explain_analyze_device_dispatches(self, db):
        db.sql(_window_sql())  # warm the class + layout
        res = db.sql("EXPLAIN ANALYZE " + _window_sql())
        analyze = next(r[1] for r in res.rows
                       if r[0].startswith("analyze (cold"))
        line = next(l for l in analyze.splitlines()
                    if l.startswith("device_dispatches:"))
        # warm bm-class query = ONE device dispatch, cold and warm runs
        assert line == "device_dispatches: 1 (warm: 1)", analyze

    def test_dispatch_stats_counter_moves(self, db):
        from greptimedb_tpu.query.physical import DISPATCH_STATS

        before = DISPATCH_STATS["dispatches"]
        db.sql(_window_sql())
        assert DISPATCH_STATS["dispatches"] == before + 1


# ---------------------------------------------------------------------------
# where_series stacked dispatch (PR-7 follow-up)
# ---------------------------------------------------------------------------

class TestFilteredStacking:
    def test_engine_batch_tag_filtered_bit_exact(self, db):
        from greptimedb_tpu.query.parser import parse_sql

        hosts = ["host_0", "host_1", "host_2", "host_1"]
        sels = [parse_sql(_window_sql(h))[0] for h in hosts]
        solo = [db.engine.execute_select(s)
                for s in (parse_sql(_window_sql(h))[0] for h in hosts)]
        batched = db.engine.execute_select_batch(sels)
        assert batched is not None, "tag-filtered windows did not stack"
        for b, s in zip(batched, solo):
            assert b.column_names == s.column_names
            assert b.rows == s.rows  # bit-exact vs solo

    def test_mixed_filtered_and_unfiltered_falls_back(self, db):
        from greptimedb_tpu.query.parser import parse_sql

        sels = [parse_sql(_window_sql("host_0"))[0],
                parse_sql(_window_sql(None))[0]]
        assert db.engine.execute_select_batch(sels) is None

    def test_field_predicate_does_not_stack(self, db):
        from greptimedb_tpu.query.parser import parse_sql

        q = (
            "SELECT h, date_trunc('hour', ts) AS hour, avg(v) FROM cpu "
            f"WHERE v > 10 AND ts >= {T0} AND ts < {T0 + 3600_000} "
            "GROUP BY h, hour"
        )
        sels = [parse_sql(q)[0], parse_sql(q)[0]]
        # identical fingerprints but an elementwise WHERE: the stacked
        # bm path must refuse (solo path handles it correctly)
        assert db.engine.execute_select_batch(sels) is None


# ---------------------------------------------------------------------------
# Persistent cache + AOT warmup across a restart
# ---------------------------------------------------------------------------

def _boot_and_query(d, sql):
    db = GreptimeDB(d)
    try:
        return db, db.sql(sql)
    except Exception:
        db.close()
        raise


class TestPersistentCache:
    def _seed(self, tmp_path):
        d = str(tmp_path / "data")
        db = GreptimeDB(d)
        _fill(db)
        want = db.sql(_window_sql())
        db.sql(_window_sql())  # warm = the journaled class
        db.close()
        return d, want

    def test_second_boot_zero_xla_builds(self, tmp_path):
        d, want = self._seed(tmp_path)
        b0 = REGISTRY.value('greptime_compile_xla_builds_total', ("sql",))
        db2, got = _boot_and_query(d, _window_sql())
        try:
            b1 = REGISTRY.value(
                "greptime_compile_xla_builds_total", ("sql",))
            assert b1 - b0 == 0, "second boot compiled"
            assert got.rows == want.rows
            assert db2.plan_compiler.aot_hits > 0
            assert db2.warmup is not None and db2.warmup.warmed > 0
        finally:
            db2.close()

    def test_corrupt_cache_recompiles_never_wrong(self, tmp_path):
        d, want = self._seed(tmp_path)
        for path in glob.glob(
                os.path.join(d, "compile_cache", "aot", "*.gtc")):
            with open(path, "r+b") as f:
                f.seek(max(0, os.path.getsize(path) // 2))
                f.write(b"\x00garbage\x00")
        b0 = REGISTRY.value('greptime_compile_xla_builds_total', ("sql",))
        db2, got = _boot_and_query(d, _window_sql())
        try:
            assert got.rows == want.rows  # NEVER a wrong result
            assert db2.plan_compiler.store.corrupt > 0
            assert glob.glob(os.path.join(
                d, "compile_cache", "quarantine", "*"))
            assert REGISTRY.value(
                "greptime_compile_xla_builds_total", ("sql",)) > b0
        finally:
            db2.close()

    def test_truncated_cache_recompiles(self, tmp_path):
        d, want = self._seed(tmp_path)
        for path in glob.glob(
                os.path.join(d, "compile_cache", "aot", "*.gtc")):
            with open(path, "r+b") as f:
                f.truncate(100)
        db2, got = _boot_and_query(d, _window_sql())
        try:
            assert got.rows == want.rows
            assert db2.plan_compiler.store.corrupt > 0
        finally:
            db2.close()

    def test_stale_jaxlib_entries_evicted(self, tmp_path):
        from greptimedb_tpu.compile.store import (
            decode_envelope, encode_envelope,
        )

        d, want = self._seed(tmp_path)
        paths = glob.glob(os.path.join(d, "compile_cache", "aot", "*.gtc"))
        for path in paths:
            with open(path, "rb") as f:
                doc = pickle.loads(decode_envelope(f.read()))
            doc["env"] = dict(doc["env"], jaxlib="0.0.1")
            with open(path, "wb") as f:
                f.write(encode_envelope(pickle.dumps(doc)))
        db2, got = _boot_and_query(d, _window_sql())
        try:
            assert got.rows == want.rows
            assert db2.plan_compiler.store.stale > 0
            # the stale-content artifacts were evicted; paths that exist
            # again are fresh re-persists recorded under the CURRENT env
            for path in paths:
                if not os.path.exists(path):
                    continue
                with open(path, "rb") as f:
                    doc = pickle.loads(decode_envelope(f.read()))
                assert doc["env"] == db2.plan_compiler.store.env
        finally:
            db2.close()

    def test_concurrent_instances_share_cache_dir(self, tmp_path,
                                                  monkeypatch):
        shared = str(tmp_path / "shared_cc")
        monkeypatch.setenv('GREPTIME_COMPILE_CACHE_DIR', shared)
        dbs = [GreptimeDB(str(tmp_path / f"d{i}")) for i in range(2)]
        try:
            for db in dbs:
                _fill(db)
            results: dict[int, object] = {}
            errs: list = []

            def worker(i):
                try:
                    for _ in range(3):
                        results[i] = dbs[i].sql(_window_sql())
                except Exception as e:  # noqa: BLE001
                    errs.append(e)

            ts = [threading.Thread(target=worker, args=(i,))
                  for i in range(2)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            assert not errs, errs
            assert results[0].rows == results[1].rows
        finally:
            for db in dbs:
                db.close()

    def test_journal_and_workload_registration(self, tmp_path):
        d, _want = self._seed(tmp_path)
        with open(os.path.join(d, "compile_cache", "usage.json"),
                  "rb") as f:
            from greptimedb_tpu.compile.store import decode_envelope

            doc = json.loads(decode_envelope(f.read(), b"GTJ1 "))
        assert doc["v"] == 1 and doc["classes"]
        assert any(e.get('replay', {}) and e["replay"].get("kind") ==
                   "sql_plan" for e in doc["classes"].values())
        db2 = GreptimeDB(d)
        try:
            usage = db2.memory.usage()
            assert usage["compile_cache"]["kind"] == "disk"
            assert usage["compile_cache"]["used_bytes"] > 0
        finally:
            db2.close()

    def test_cache_off_knob_writes_nothing(self, tmp_path, monkeypatch):
        monkeypatch.setenv('GREPTIME_COMPILE_CACHE', "off")
        d = str(tmp_path / "off")
        db = GreptimeDB(d)
        try:
            _fill(db)
            db.sql(_window_sql())
            assert db.plan_compiler.store is None
            assert not os.path.exists(os.path.join(d, "compile_cache"))
        finally:
            db.close()

    def test_warmup_survives_dropped_table(self, tmp_path):
        d, _want = self._seed(tmp_path)
        db2 = GreptimeDB(d)
        try:
            db2.sql("DROP TABLE cpu")
        finally:
            db2.close()
        db3 = GreptimeDB(d)  # replays against a missing table
        try:
            assert db3.warmup is None or db3.warmup.errors >= 0
            assert db3.sql("SELECT 1").rows == [[1]]
        finally:
            db3.close()

    def test_subquery_tql_classes_keep_their_replay(self, tmp_path):
        """Nested evaluators (subquery operands) are constructed MID-
        statement and must not strip the outer TQL's replay context —
        every promql class this statement builds journals warmable."""
        from greptimedb_tpu.compile.store import decode_envelope

        d = str(tmp_path / "data")
        db = GreptimeDB(d)
        try:
            _fill(db)
            lo = T0 // 1000
            db.sql(f"TQL EVAL ({lo + 900}, {lo + 1800}, 120) "
                   'sum by (h) (max_over_time('
                   'rate(cpu{__field__="v"}[3m])[10m:2m]))')
        finally:
            db.close()
        with open(os.path.join(d, "compile_cache", "usage.json"),
                  "rb") as f:
            doc = json.loads(decode_envelope(f.read(), b"GTJ1 "))
        promql = [e for e in doc["classes"].values()
                  if e["engine"] == "promql"]
        assert promql, "no promql classes journaled"
        for e in promql:
            assert e.get("replay"), e
            assert e["replay"]["kind"] == "tql"

    def test_warmup_replays_do_not_self_count(self, tmp_path):
        from greptimedb_tpu.compile.store import decode_envelope

        d, _want = self._seed(tmp_path)

        def counts():
            with open(os.path.join(d, "compile_cache", "usage.json"),
                      "rb") as f:
                doc = json.loads(decode_envelope(f.read(), b"GTJ1 "))
            return {cid: e["count"] for cid, e in doc["classes"].items()}

        before = counts()
        db2, _got = _boot_and_query(d, _window_sql())
        db2.close()
        after = counts()
        # warmup replayed the class and the real query hit the warmed
        # in-memory cache: neither may re-increment the journal ranking
        for cid, c in before.items():
            assert after[cid] == c, (cid, c, after[cid])

    def test_dropped_table_classes_tombstone(self, tmp_path):
        from greptimedb_tpu.compile.journal import UsageJournal

        d, _want = self._seed(tmp_path)
        db2 = GreptimeDB(d)
        try:
            db2.sql("DROP TABLE cpu")
        finally:
            db2.close()
        db3 = GreptimeDB(d)  # warmup replays hit TableNotFound
        try:
            assert db3.warmup is not None and db3.warmup.errors > 0
        finally:
            db3.close()
        j = UsageJournal(os.path.join(d, "compile_cache", "usage.json"))
        assert j.top(None) == []  # nothing left to burn boot budget on

    def test_scheduler_idle_tick_drains_warmup(self, tmp_path):
        d, _want = self._seed(tmp_path)
        os.environ["GREPTIME_AOT_WARMUP_TOP_K"] = "0"
        try:
            db2 = GreptimeDB(d)
        finally:
            os.environ.pop("GREPTIME_AOT_WARMUP_TOP_K")
        try:
            if db2.warmup is None:
                pytest.skip("no journaled classes")
            assert db2.warmup.pending()
            assert db2.scheduler.idle_hook is not None
            # force the scheduler to start its worker, then wait for the
            # idle ticks to drain the queue
            db2.scheduler.submit("SELECT 1")
            import time as _t

            deadline = _t.monotonic() + 10
            while db2.warmup.pending() and _t.monotonic() < deadline:
                _t.sleep(0.05)
            assert not db2.warmup.pending()
            assert db2.warmup.warmed > 0
        finally:
            db2.close()
