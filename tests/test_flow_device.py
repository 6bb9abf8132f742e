"""Device flow runtime: one-dispatch folds, device/host parity fuzz,
GTF1 checkpoint + WAL-tail resume, quota fallback, mesh parity, chaos
flownode kill/resume (ISSUE 14 / VERDICT item 7).
"""

import os

import numpy as np
import pytest

from greptimedb_tpu.standalone import GreptimeDB

pytestmark = []


@pytest.fixture
def db():
    d = GreptimeDB()
    yield d
    d.close()


@pytest.fixture
def host_db(monkeypatch):
    """The A/B twin: GREPTIME_FLOW_DEVICE=off keeps the host
    dict-of-partials engine byte-for-byte."""
    monkeypatch.setenv("GREPTIME_FLOW_DEVICE", "off")
    d = GreptimeDB()
    assert d.flow_runtime is None
    yield d
    d.close()


def _mk_source(d, name="src"):
    d.sql(f"CREATE TABLE {name} (h STRING, ts TIMESTAMP(3) TIME INDEX, "
          "v DOUBLE, k BIGINT, PRIMARY KEY (h))")


FLOW_SQL = ("CREATE FLOW {name} SINK TO {sink} AS SELECT "
            "date_bin(INTERVAL '1 minute', ts) AS w, h, sum(v) AS s, "
            "count(*) AS c, count(v) AS cv, avg(v) AS a, min(v) AS mn, "
            "max(v) AS mx, first_value(v) AS fv, last_value(v) AS lv, "
            "sum(k) AS sk FROM {src} GROUP BY w, h")


def _seeded_batches(seed, nbatches=8, rows=24, hosts=6, null_every=7,
                    ordered=False):
    """Deterministic ingest batches: integer-valued doubles (exactly
    representable -> additive folds are associative, so device/host
    parity can demand equality), growing tag vocabulary, NULLs.

    ``ordered=False`` scatters timestamps across all windows seen so far
    (out-of-order/late rows -> non-appendable batches: BOTH engines
    reseed, by the shared appendability classification).
    ``ordered=True`` keeps timestamps strictly increasing (the
    time-series hot path: every batch pumps through the incremental
    one-dispatch fold and the WAL tail replays cleanly)."""
    rng = np.random.default_rng(seed)
    batches = []
    t = 0
    for b in range(nbatches):
        vals = []
        for j in range(rows):
            # vocabulary growth: later batches introduce new hosts
            h = f"h{rng.integers(0, hosts + b)}"
            if ordered:
                t += int(rng.integers(500, 4_000))
                ts = t
            else:
                # out-of-order + late: timestamps scatter across all
                # windows seen so far, including already-folded ones
                ts = int(rng.integers(0, (b + 1) * 120_000))
            if (b * rows + j) % null_every == 0:
                v = "NULL"
            else:
                v = f"{float(rng.integers(-50, 100))}"
            k = int(rng.integers(-1000, 1000))
            vals.append(f"('{h}', {ts}, {v}, {k})")
        batches.append("INSERT INTO src VALUES " + ", ".join(vals))
    return batches


def _sink_rows(d, sink="agg"):
    return d.sql(
        f"SELECT w, h, s, c, cv, a, mn, mx, fv, lv, sk FROM {sink} "
        "ORDER BY w, h").rows


class TestDeviceEligibility:
    def test_full_agg_surface_goes_device(self, db):
        _mk_source(db)
        db.sql(FLOW_SQL.format(name="f", sink="agg", src="src"))
        db.sql("INSERT INTO src VALUES ('x', 1000, 1.0, 2)")
        task = db.flow_engine.flows["f"]
        assert task.device_state is not None
        assert not task.device_failed
        assert db.flow_runtime.fold_dispatches >= 1

    def test_where_clause_stays_host(self, db):
        _mk_source(db)
        db.sql("CREATE FLOW f SINK TO agg AS SELECT h, sum(v) AS s "
               "FROM src WHERE v > 0 GROUP BY h")
        db.sql("INSERT INTO src VALUES ('x', 1000, 1.0, 2)")
        task = db.flow_engine.flows["f"]
        assert task.device_state is None
        # ...but the host fold still carries an exact watermark now
        assert task.watermark

    def test_sketch_agg_stays_host(self, db):
        # hll sketch states are python objects: outside the device fold's
        # closed surface, the flow streams on the host engine
        _mk_source(db)
        db.sql("CREATE FLOW f SINK TO agg AS SELECT h, "
               "approx_distinct(v) AS m FROM src GROUP BY h")
        db.sql("INSERT INTO src VALUES ('x', 1000, 1.0, 2), "
               "('x', 2000, 3.0, 2)")
        assert db.flow_engine.flows["f"].device_state is None
        assert db.sql("SELECT m FROM agg ORDER BY update_at DESC LIMIT 1"
                      ).rows == [[2.0]]


class TestOneDispatchPin:
    def test_warm_fold_is_one_dispatch(self, db):
        from greptimedb_tpu.query.physical import DISPATCH_STATS

        _mk_source(db)
        db.sql(FLOW_SQL.format(name="f", sink="agg", src="src"))
        # cold: seed + group/window discovery
        db.sql("INSERT INTO src VALUES ('x', 1000, 1.0, 2), "
               "('y', 2000, 2.0, 3)")
        # warm: same groups and windows, no growth
        d0 = DISPATCH_STATS["dispatches"]
        db.sql("INSERT INTO src VALUES ('x', 3000, 3.0, 4), "
               "('y', 4000, 4.0, 5)")
        assert DISPATCH_STATS["dispatches"] - d0 == 1

    def test_fold_counter_exported(self, db):
        from greptimedb_tpu.utils.telemetry import REGISTRY

        _mk_source(db)
        db.sql(FLOW_SQL.format(name="f", sink="agg", src="src"))
        db.sql("INSERT INTO src VALUES ('x', 1000, 1.0, 2)")
        assert REGISTRY.value(
            "greptime_flow_fold_dispatches_total", ("f",)) >= 1


class TestDeviceHostParity:
    @pytest.mark.parametrize("seed,ordered", [(3, False), (11, False),
                                              (29, True), (43, True)])
    def test_streaming_fold_parity_fuzz(self, seed, ordered, db, host_db):
        """All aggregate kinds x out-of-order/late rows x NULLs x vocab
        growth: device and host sinks must match exactly.  Ordered seeds
        exercise the warm incremental pump; unordered ones the shared
        reseed-on-upsertable-write path."""
        for d in (db, host_db):
            _mk_source(d)
            d.sql(FLOW_SQL.format(name="f", sink="agg", src="src"))
        for stmt in _seeded_batches(seed, ordered=ordered):
            db.sql(stmt)
            host_db.sql(stmt)
        if ordered:
            # the time-forward stream stayed incremental: one reseed at
            # flow creation (the seed itself), never again
            assert db.flow_runtime.reseeds <= 1
        dev, host = _sink_rows(db), _sink_rows(host_db)
        assert db.flow_engine.flows["f"].device_state is not None
        assert len(dev) == len(host)
        for dr, hr in zip(dev, host):
            assert dr == hr
        # ...and both equal a fresh re-query over the full source.
        # first/last_value are excluded on the incremental (ordered)
        # runs: the PICK-PAIR decomposition both engines share diverges
        # from the whole-query eval when a NULL value sits at a window's
        # extreme timestamp (the chunk companion still advances) — a
        # pre-existing host-engine trait the device fold mirrors exactly.
        requeried = db.sql(
            "SELECT date_bin(INTERVAL '1 minute', ts) AS w, h, sum(v), "
            "count(*), count(v), avg(v), min(v), max(v), first_value(v), "
            "last_value(v), sum(k) FROM src GROUP BY w, h ORDER BY w, h"
        ).rows
        if ordered:
            drop = (8, 9)  # fv, lv
            requeried = [[c for i, c in enumerate(r) if i not in drop]
                         for r in requeried]
            dev = [[c for i, c in enumerate(r) if i not in drop]
                   for r in dev]
        assert dev == requeried

    def test_expire_parity(self, db, host_db):
        import time as _t

        now = int(_t.time() * 1000)
        for d in (db, host_db):
            _mk_source(d)
            d.sql("CREATE FLOW f SINK TO agg EXPIRE AFTER '1 hour' AS "
                  "SELECT date_bin(INTERVAL '1 minute', ts) AS w, h, "
                  "sum(v) AS s FROM src GROUP BY w, h")
            # live rows, then a late row into an expired (1970) window
            d.sql(f"INSERT INTO src VALUES ('x', {now}, 2.0, 1)")
            d.sql("INSERT INTO src VALUES ('x', 1000, 5.0, 1)")
        dev = db.sql("SELECT h, s FROM agg ORDER BY w, h").rows
        host = host_db.sql("SELECT h, s FROM agg ORDER BY w, h").rows
        assert dev == host
        # expired window pruned from live state on both engines
        assert db.flow_engine.state_keys("f") == \
            host_db.flow_engine.state_keys("f")

    def test_upsert_forces_reseed_parity(self, db, host_db):
        for d in (db, host_db):
            _mk_source(d)
            d.sql("CREATE FLOW f SINK TO agg AS SELECT "
                  "date_bin(INTERVAL '1 minute', ts) AS w, h, sum(v) AS s "
                  "FROM src GROUP BY w, h")
            d.sql("INSERT INTO src VALUES ('x', 1000, 1.0, 1)")
            d.sql("INSERT INTO src VALUES ('x', 1000, 5.0, 1)")  # upsert!
            d.sql("INSERT INTO src VALUES ('x', 2000, 2.0, 1)")
        assert db.sql("SELECT s FROM agg").rows == [[7.0]]
        assert host_db.sql("SELECT s FROM agg").rows == [[7.0]]

    def test_multi_key_and_int_tag_parity(self, db, host_db):
        for d in (db, host_db):
            d.sql("CREATE TABLE m (a STRING, b STRING, code BIGINT, "
                  "ts TIMESTAMP(3) TIME INDEX, v DOUBLE, "
                  "PRIMARY KEY (a, b, code))")
            d.sql("CREATE FLOW f SINK TO agg AS SELECT a, b, code, "
                  "sum(v) AS s, count(*) AS c FROM m GROUP BY a, b, code")
            rng = np.random.default_rng(7)
            for _ in range(4):
                vals = ", ".join(
                    f"('a{rng.integers(0, 4)}', 'b{rng.integers(0, 3)}', "
                    f"{rng.integers(200, 205)}, {rng.integers(0, 10_000)}, "
                    f"{float(rng.integers(1, 50))})"
                    for _ in range(16))
                d.sql(f"INSERT INTO m VALUES {vals}")
        q = "SELECT a, b, code, s, c FROM agg ORDER BY a, b, code"
        assert db.flow_engine.flows["f"].device_state is not None
        assert db.sql(q).rows == host_db.sql(q).rows


class TestMeshParity:
    def test_mesh_sharded_matches_single_device(self, db, monkeypatch):
        """conftest forces 8 host devices, so the default db shards flow
        state across the mesh; GREPTIME_MESH=off is the single-device
        twin."""
        monkeypatch.setenv("GREPTIME_MESH", "off")
        solo = GreptimeDB()
        try:
            for d in (db, solo):
                _mk_source(d)
                d.sql(FLOW_SQL.format(name="f", sink="agg", src="src"))
            for stmt in _seeded_batches(17, nbatches=5):
                db.sql(stmt)
                solo.sql(stmt)
            if db.mesh is not None:
                st = db.flow_engine.flows["f"].device_state
                assert st is not None and st.shardings is not None
            assert _sink_rows(db) == _sink_rows(solo)
        finally:
            solo.close()


class TestQuotaFallback:
    def test_reject_to_host_fallback(self, monkeypatch):
        from greptimedb_tpu.utils.telemetry import REGISTRY

        monkeypatch.setenv("GREPTIME_FLOW_QUOTA_BYTES", "1")
        d = GreptimeDB()
        try:
            _mk_source(d)
            d.sql(FLOW_SQL.format(name="f", sink="agg", src="src"))
            d.sql("INSERT INTO src VALUES ('x', 1000, 1.0, 2), "
                  "('y', 61000, 2.0, 3)")
            task = d.flow_engine.flows["f"]
            assert task.device_state is None and task.device_failed
            assert d.memory.usage()["flow"]["rejected"] >= 1
            assert REGISTRY.value(
                "greptime_flow_fallback_total", ("quota",)) >= 1
            # the host fallback still answers correctly
            assert d.sql("SELECT h, s FROM agg ORDER BY h").rows == [
                ["x", 1.0], ["y", 2.0]]
        finally:
            d.close()


class TestCheckpointResume:
    def test_clean_restart_restores_without_reseed(self, tmp_path):
        home = str(tmp_path / "d")
        d = GreptimeDB(home)
        _mk_source(d)
        d.sql(FLOW_SQL.format(name="f", sink="agg", src="src"))
        for stmt in _seeded_batches(5, nbatches=3):
            d.sql(stmt)
        before = _sink_rows(d)
        d.close()  # graceful: checkpoints every dirty flow

        d2 = GreptimeDB(home)
        task = d2.flow_engine.flows["f"]
        assert task.restored_from_checkpoint
        assert d2.flow_runtime.last_restore.get("f") == "checkpoint"
        assert d2.flow_runtime.reseeds == 0  # no re-backfill
        assert _sink_rows(d2) == before
        # streaming continues from the restored state
        d2.sql("INSERT INTO src VALUES ('h0', 1000, 3.0, 1)")
        requeried = d2.sql(
            "SELECT date_bin(INTERVAL '1 minute', ts) AS w, h, sum(v), "
            "count(*), count(v), avg(v), min(v), max(v), first_value(v), "
            "last_value(v), sum(k) FROM src GROUP BY w, h ORDER BY w, h"
        ).rows
        assert _sink_rows(d2) == requeried
        d2.close()

    def test_crash_resumes_by_wal_tail_replay(self, tmp_path):
        """Checkpoint at T, more acked writes, CRASH (no final
        checkpoint): restart restores the T state and replays only the
        WAL tail past the watermark — bit-exact vs an uninterrupted
        twin, nothing lost, nothing double-folded."""
        from greptimedb_tpu.utils.telemetry import REGISTRY

        home = str(tmp_path / "d")
        twin_home = str(tmp_path / "twin")
        d = GreptimeDB(home)
        twin = GreptimeDB(twin_home)
        for x in (d, twin):
            _mk_source(x)
            x.sql(FLOW_SQL.format(name="f", sink="agg", src="src"))
        batches = _seeded_batches(23, nbatches=6, ordered=True)
        for stmt in batches[:3]:
            d.sql(stmt)
            twin.sql(stmt)
        assert d.flow_engine.checkpoint_now("f") >= 1  # watermark at batch 3
        for stmt in batches[3:]:
            d.sql(stmt)
            twin.sql(stmt)
        # crash: no shutdown checkpoint, WAL holds the acked tail
        d.flow_checkpoints = None
        d.close()

        replays0 = REGISTRY.value(
            "greptime_flow_checkpoint_total", ("tail_replay",))
        d2 = GreptimeDB(home)
        task = d2.flow_engine.flows["f"]
        assert task.restored_from_checkpoint
        assert d2.flow_runtime.reseeds == 0  # tail replay, NOT re-backfill
        assert REGISTRY.value(
            "greptime_flow_checkpoint_total", ("tail_replay",)) > replays0
        assert _sink_rows(d2) == _sink_rows(twin)
        d2.close()
        twin.close()

    def test_upsert_within_tail_reseeds_not_double_counts(self, tmp_path):
        """Review repro: checkpoint, append a tail row, then UPSERT that
        same tail row, crash.  The tail now contains both the original
        and the overwriting row — replaying both would double-count
        (sum showed 7.0 for a true 6.0).  Restore must detect the
        overlap and reseed instead."""
        home = str(tmp_path / "d")
        d = GreptimeDB(home)
        _mk_source(d)
        d.sql("CREATE FLOW f SINK TO agg AS SELECT "
              "date_bin(INTERVAL '1 minute', ts) AS w, h, sum(v) AS s "
              "FROM src GROUP BY w, h")
        d.sql("INSERT INTO src VALUES ('x', 1000, 1.0, 1)")
        d.flow_engine.checkpoint_now()
        d.sql("INSERT INTO src VALUES ('x', 2000, 1.0, 1)")  # tail append
        d.sql("INSERT INTO src VALUES ('x', 2000, 5.0, 1)")  # tail UPSERT
        d.flow_checkpoints = None  # crash: no shutdown checkpoint
        d.close()

        d2 = GreptimeDB(home)
        # tail not cleanly replayable -> reseed fallback, never 7.0
        d2.sql("INSERT INTO src VALUES ('x', 3000, 2.0, 1)")
        assert d2.sql("SELECT s FROM agg").rows == [[8.0]]  # 1+5+2
        d2.close()

    def test_corrupt_checkpoint_quarantines_and_reseeds(self, tmp_path):
        home = str(tmp_path / "d")
        d = GreptimeDB(home)
        _mk_source(d)
        d.sql(FLOW_SQL.format(name="f", sink="agg", src="src"))
        d.sql("INSERT INTO src VALUES ('x', 1000, 1.0, 2)")
        d.close()
        path = os.path.join(home, "flow_ckpt", "f.ckpt")
        blob = bytearray(open(path, "rb").read())
        blob[len(blob) // 2] ^= 0xFF
        open(path, "wb").write(bytes(blob))

        d2 = GreptimeDB(home)
        task = d2.flow_engine.flows["f"]
        assert not task.restored_from_checkpoint
        assert os.path.exists(path + ".quarantine")
        # reseed path still serves the right answer
        d2.sql("INSERT INTO src VALUES ('x', 2000, 2.0, 2)")
        assert d2.sql("SELECT s FROM agg").rows == [[3.0]]
        d2.close()

    def test_host_stream_checkpoint_resume(self, tmp_path):
        """Device-ineligible (WHERE) flows checkpoint their host
        dict-of-partials with the same exact watermark."""
        home = str(tmp_path / "d")
        d = GreptimeDB(home)
        _mk_source(d)
        d.sql("CREATE FLOW f SINK TO agg AS SELECT h, sum(v) AS s "
              "FROM src WHERE v > 0 GROUP BY h")
        d.sql("INSERT INTO src VALUES ('x', 1000, 5.0, 1), "
              "('x', 2000, -3.0, 1)")
        assert d.flow_engine.flows["f"].device_state is None
        d.close()

        d2 = GreptimeDB(home)
        task = d2.flow_engine.flows["f"]
        assert task.restored_from_checkpoint
        assert task.stream_state  # state came from the checkpoint
        d2.sql("INSERT INTO src VALUES ('x', 3000, 2.0, 1)")
        assert d2.sql("SELECT s FROM agg ORDER BY update_at DESC LIMIT 1"
                      ).rows == [[7.0]]
        d2.close()


@pytest.mark.chaos
class TestFlownodeChaos:
    def test_kill_flownode_mid_stream_resumes_bit_exact(self, tmp_path):
        """VERDICT item 7's flownode-reassignment chaos case: kill the
        owner mid-stream under seeded ingest; the reassigned node resumes
        from the checkpoint + WAL tail with zero lost and zero duplicated
        sink rows, bit-exact vs an uninterrupted twin."""
        from greptimedb_tpu.flow.cluster import FlowControlPlane, Flownode
        from greptimedb_tpu.query.parser import parse_sql

        d = GreptimeDB(str(tmp_path / "d"))
        twin = GreptimeDB(str(tmp_path / "twin"))
        for x in (d, twin):
            _mk_source(x)
        stmt_sql = FLOW_SQL.format(name="f", sink="agg", src="src")

        plane = FlowControlPlane(d.kv)
        nodes = [Flownode(i, d) for i in range(2)]
        for n in nodes:
            plane.register_flownode(n)
        owner_id = plane.create_flow(parse_sql(stmt_sql)[0])
        twin.sql(stmt_sql)

        rng_batches = _seeded_batches(41, nbatches=6, ordered=True)

        def ingest(x_db, notify, stmt):
            # drive the plane's mirror dispatch the way a frontend would
            x_db.sql(stmt) if notify is None else None
            if notify is not None:
                import re

                rows = re.findall(r"\(([^)]*)\)", stmt.split("VALUES", 1)[1])
                cols = {"h": [], "ts": [], "v": [], "k": []}
                for r in rows:
                    h, ts, v, k = [p.strip() for p in r.split(",")]
                    cols["h"].append(h.strip("'"))
                    cols["ts"].append(int(ts))
                    cols["v"].append(None if v == "NULL" else float(v))
                    cols["k"].append(int(k))
                region = x_db._region_of("src")
                region.write(dict(cols))
                notify.on_write("src", cols["ts"], cols, appendable=True)

        for stmt in rng_batches[:3]:
            ingest(d, plane, stmt)
            ingest(twin, None, stmt)
        # checkpoint mid-stream, then kill the owner
        owner = plane.nodes[owner_id]
        assert owner.engine.checkpoint_now("f") >= 1
        for stmt in rng_batches[3:5]:
            ingest(d, plane, stmt)
            ingest(twin, None, stmt)
        owner.alive = False

        moved = plane.tick(now_ms=1.0)
        assert moved == ["f"]
        new_owner = plane.nodes[plane.route("f")]
        task = new_owner.engine.flows["f"]
        # resumed from checkpoint + tail, not a full re-backfill
        assert task.restored_from_checkpoint
        assert new_owner.engine.runtime.last_restore.get("f") == "checkpoint"
        # stream continues on the survivor
        for stmt in rng_batches[5:]:
            ingest(d, plane, stmt)
            ingest(twin, None, stmt)
        plane.run_all()
        twin.flow_engine.run_all()
        assert _sink_rows(d) == _sink_rows(twin)
        d.close()
        twin.close()

    def test_batching_watermark_survives_upsert_gap(self, tmp_path):
        """Review regression: an unlogged sequence (upsert) must not
        freeze the batching watermark forever — the gap's windows mark
        from the memtable copy and the watermark advances past it."""
        d = GreptimeDB(str(tmp_path / "d"))
        _mk_source(d)
        d.sql("CREATE FLOW fb SINK TO aggb AS SELECT "
              "date_bin(INTERVAL '1 minute', ts) AS w, h, "
              "count(DISTINCT v) AS dv FROM src GROUP BY w, h")
        task = d.flow_engine.flows["fb"]
        d.sql("INSERT INTO src VALUES ('a', 1000, 1.0, 0)")
        d.sql("INSERT INTO src VALUES ('a', 1000, 2.0, 0)")  # upsert: gap
        d.sql("INSERT INTO src VALUES ('a', 61000, 3.0, 0)")
        rid = d._region_of("src").region_id
        assert task.watermark[rid] == 3  # advanced THROUGH the gap
        assert d.sql("SELECT w, dv FROM aggb ORDER BY w").rows == [
            [0, 1.0], [60_000, 1.0]]
        d.close()

    def test_batching_failover_resumes_from_watermark(self, tmp_path):
        """The _mark_full_range_dirty fix: with a checkpoint, a batching
        flow re-marks only the windows past its watermark instead of the
        full source range."""
        from greptimedb_tpu.flow.cluster import FlowControlPlane, Flownode
        from greptimedb_tpu.query.parser import parse_sql

        d = GreptimeDB(str(tmp_path / "d"))
        _mk_source(d)
        plane = FlowControlPlane(d.kv)
        nodes = [Flownode(i, d) for i in range(2)]
        for n in nodes:
            plane.register_flownode(n)
        owner_id = plane.create_flow(parse_sql(
            "CREATE FLOW fb SINK TO aggb AS SELECT "
            "date_bin(INTERVAL '1 minute', ts) AS w, h, "
            "count(DISTINCT v) AS dv FROM src GROUP BY w, h")[0])
        owner = plane.nodes[owner_id]
        assert owner.engine.flows["fb"].mode == "batching"

        region = d._region_of("src")
        early = {"h": ["a"] * 4, "ts": [0, 1_000, 61_000, 121_000],
                 "v": [1.0, 2.0, 3.0, 4.0], "k": [0, 0, 0, 0]}
        region.write(early)
        plane.on_write("src", early["ts"], early, appendable=True)
        plane.run_all()
        assert owner.engine.checkpoint_now("fb") >= 1
        assert os.path.exists(owner.engine.checkpoints.path("fb"))

        # writes during the outage land in ONE late window
        owner.alive = False
        late = {"h": ["a"], "ts": [301_000], "v": [9.0], "k": [0]}
        region.write(late)
        plane.on_write("src", late["ts"], late, appendable=True)

        moved = plane.tick(now_ms=1.0)
        assert moved == ["fb"]
        task = plane.nodes[plane.route("fb")].engine.flows["fb"]
        assert task.restored_from_checkpoint
        # only the tail window re-marked — NOT windows 0/60000/120000
        assert task.dirty == {300_000}
        plane.run_all()
        rows = d.sql("SELECT w, dv FROM aggb ORDER BY w").rows
        assert rows == [[0, 2.0], [60_000, 1.0], [120_000, 1.0],
                        [300_000, 1.0]]
        d.close()


class TestIntrospection:
    def test_show_flows_extended_columns(self, db):
        _mk_source(db)
        db.sql(FLOW_SQL.format(name="f", sink="agg", src="src"))
        db.sql("INSERT INTO src VALUES ('x', 1000, 1.0, 2)")
        res = db.sql("SHOW FLOWS")
        assert res.column_names == [
            "Flow", "Sink", "Source", "Comment", "Mode", "Flownode",
            "StateBytes", "Watermark", "LastTick"]
        row = res.rows[0]
        assert row[0] == "f" and row[4] == "streaming(device)"
        assert row[6] > 0 and row[7] is not None and row[8] > 0

    def test_information_schema_flows(self, db):
        _mk_source(db)
        db.sql(FLOW_SQL.format(name="f", sink="agg", src="src"))
        db.sql("INSERT INTO src VALUES ('x', 1000, 1.0, 2)")
        r = db.sql(
            "SELECT flow_name, mode, state_size, checkpoint_watermark, "
            "flow_definition FROM information_schema.flows")
        assert r.rows[0][0] == "f"
        assert r.rows[0][1] == "streaming(device)"
        assert r.rows[0][2] > 0
        assert r.rows[0][3] is not None
        assert "date_bin" in r.rows[0][4]


class TestMemProfEndpoint:
    def test_debug_prof_mem(self, db):
        import json
        import urllib.request

        from greptimedb_tpu.servers import HttpServer

        srv = HttpServer(db, port=0)
        srv.start()
        try:
            def get(path):
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{srv.port}{path}") as r:
                    return json.loads(r.read())

            out = get("/debug/prof/mem?action=start")
            assert out["tracing"] is True
            # allocate something attributable
            _mk_source(db)
            db.sql(FLOW_SQL.format(name="f", sink="agg", src="src"))
            db.sql("INSERT INTO src VALUES ('x', 1000, 1.0, 2)")
            out = get("/debug/prof/mem?top=5")
            assert out["tracing"] is True
            assert len(out["top"]) <= 5 and out["top"]
            assert "diff" in out
            assert out["traced_bytes"] > 0
            # HBM side: workload budgets, flow workload present
            assert "flow" in out["workloads"]
            assert out["workloads"]["flow"]["kind"] == "hbm"
            assert out["hbm_used_bytes"] >= 0
            out = get("/debug/prof/mem?action=stop")
            assert out["tracing"] is False
        finally:
            srv.stop()


class TestIdleCheckpointDrain:
    def test_scheduler_idle_hook_checkpoints(self, tmp_path, monkeypatch):
        monkeypatch.setenv("GREPTIME_FLOW_CKPT_INTERVAL_S", "0.01")
        d = GreptimeDB(str(tmp_path / "d"))
        try:
            _mk_source(d)
            d.sql(FLOW_SQL.format(name="f", sink="agg", src="src"))
            d.sql("INSERT INTO src VALUES ('x', 1000, 1.0, 2)")
            assert d.scheduler is not None
            import time as _t

            deadline = _t.time() + 5
            path = os.path.join(str(tmp_path / "d"), "flow_ckpt", "f.ckpt")
            while _t.time() < deadline and not os.path.exists(path):
                _t.sleep(0.05)
            assert os.path.exists(path)  # idle tick drained the dirty flow
        finally:
            d.close()

    def test_add_idle_hook_composes(self, db):
        calls = []
        db.scheduler.add_idle_hook(lambda: calls.append("a") and False)
        db.scheduler.add_idle_hook(lambda: calls.append("b") and False)
        import time as _t

        deadline = _t.time() + 5
        while _t.time() < deadline and len(set(calls)) < 2:
            _t.sleep(0.02)
        assert {"a", "b"} <= set(calls)
