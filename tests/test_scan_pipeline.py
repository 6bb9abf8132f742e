"""Streaming cold-scan pipeline tests (storage/scan.py).

Pins the round-10 invariants: bit-exact parity of the parallel decode +
sorted-run merge against the sequential lexsort reference
(tombstones, ALTER-added columns, overlapping sequences across SSTs),
the single-source / disjoint-run fast paths, quota reject-to-sequential
fallback, the thread-count knob, the grid catch-up build, the S3
prefetch warmer, and the tier-1 guard that the hot scan path never
materializes a per-row object array for a dictionary-encoded column.
"""

import os
import threading
import time

import numpy as np
import pytest

from greptimedb_tpu.datatypes import (
    ColumnSchema,
    ConcreteDataType as T,
    Schema,
    SemanticType as S,
)
from greptimedb_tpu.storage import scan as scanmod
from greptimedb_tpu.storage.memtable import OP, SEQ, TSID, tagcode_col
from greptimedb_tpu.storage.region import RegionEngine, RegionOptions
from greptimedb_tpu.storage.scan import (
    merge_parts, read_parts, scan_threads,
)
from greptimedb_tpu.utils.memory import WorkloadMemoryManager
from greptimedb_tpu.utils.telemetry import REGISTRY


def cpu_schema():
    return Schema(
        (
            ColumnSchema("hostname", T.STRING, S.TAG),
            ColumnSchema("dc", T.STRING, S.TAG),
            ColumnSchema("ts", T.TIMESTAMP_MILLISECOND, S.TIMESTAMP),
            ColumnSchema("usage", T.FLOAT64, S.FIELD),
        )
    )


def make_region(tmp_path, name="scanpipe", options=None):
    eng = RegionEngine(
        str(tmp_path / name),
        default_options=options or RegionOptions(
            wal_enabled=False, flush_threshold_bytes=1 << 40,
            compaction_trigger_files=1 << 30,
        ),
    )
    return eng, eng.create_region(1, cpu_schema())


def write_batch(region, hosts, t0, n=20, step=1000, val0=0.0, dc=None):
    region.write({
        "hostname": [hosts[i % len(hosts)] for i in range(n)],
        "dc": [dc if dc else ("east" if i % 2 else "west")
               for i in range(n)],
        "ts": [t0 + (i // len(hosts)) * step for i in range(n)],
        "usage": [val0 + float(i) for i in range(n)],
    })


def assert_same_columns(a, b):
    assert set(a.keys()) == set(b.keys()), (sorted(a), sorted(b))
    for k in a:
        va, vb = a[k], b[k]
        assert len(va) == len(vb), (k, len(va), len(vb))
        if va.dtype.kind == "f":
            assert np.array_equal(va, vb, equal_nan=True), k
        else:
            assert np.array_equal(va, vb), k


@pytest.fixture
def scan_ab(monkeypatch, ineligible):
    def scan_ab(region, **kw):
        """(sequential lexsort, pipelined) scan outputs."""
        monkeypatch.setenv("GREPTIME_SCAN_THREADS", "1")
        with ineligible("packed_merge"):
            seq = region.scan_host(**kw)
            assert scanmod.LAST_MERGE_PATH in ("lexsort", "empty")
        monkeypatch.delenv("GREPTIME_SCAN_THREADS")
        par = region.scan_host(**kw)
        return seq, par

    return scan_ab


class TestParity:
    def test_multi_sst_overlapping_seqs_tombstones_alter(
        self, tmp_path, scan_ab
    ):
        """The kitchen-sink parity case: upserts across SSTs (overlapping
        (series, ts) keys with different sequences), delete tombstones in
        their own SST, an ALTER-added tag column midway (old SSTs
        backfill), plus live memtable rows."""
        eng, r = make_region(tmp_path)
        write_batch(r, ["h0", "h1", "h2"], t0=0, n=30)
        r.flush()
        # overlapping keys: same (series, ts) re-written => seq dedup
        # must pick the later file
        write_batch(r, ["h0", "h1", "h2"], t0=0, n=30, val0=100.0)
        r.flush()
        write_batch(r, ["h3", "h0"], t0=50_000, n=20)
        r.flush()
        # tombstones for some of the overlapping keys
        r.delete({"hostname": ["h0"], "dc": ["west"], "ts": [0]})
        r.flush()
        r.add_tag_column("az")  # old SSTs lack it; backfilled on read
        r.write({
            "hostname": ["h9"], "dc": ["east"], "az": ["az1"],
            "ts": [90_000], "usage": [7.5],
        })
        r.flush()
        write_batch(r, ["h1"], t0=120_000, n=5)  # live memtable rows
        assert len(r.sst_files) == 5

        seq, par = scan_ab(r)
        assert_same_columns(seq, par)
        assert len(par["ts"]) > 0
        # restricted ranges + column projection parity too
        seq, par = scan_ab(r, ts_range=(1000, 60_000),
                           columns=["hostname", "usage"])
        assert_same_columns(seq, par)
        eng.close()

    def test_code_path_matches_raw_values(self, tmp_path, monkeypatch):
        """with_tag_codes returns region codes that decode to exactly the
        raw scan's tag values, row for row."""
        eng, r = make_region(tmp_path)
        write_batch(r, ["a", "b", "c"], t0=0, n=30)
        r.flush()
        write_batch(r, ["b", "d"], t0=60_000, n=10)
        raw = r.scan_host()
        coded = r.scan_host(with_tag_codes=True)
        for tag in ("hostname", "dc"):
            vocab = r.encoders[tag].values()
            decoded = np.array(
                [vocab[c] for c in coded[tagcode_col(tag)]], dtype=object)
            assert np.array_equal(raw[tag], decoded), tag
            assert tag not in coded
            assert coded[tagcode_col(tag)].dtype == np.int32
        eng.close()


class TestMergePaths:
    def test_single_source_skips_sort(self, tmp_path):
        eng, r = make_region(tmp_path)
        write_batch(r, ["h0", "h1"], t0=0, n=20)
        r.flush()
        r.scan_host()
        assert scanmod.LAST_MERGE_PATH == "presorted"
        eng.close()

    def test_disjoint_single_series_concat(self, tmp_path):
        """Time-disjoint single-series SSTs: key ranges don't interleave,
        so the merged output is an ordered concat — no row-level work."""
        eng, r = make_region(tmp_path)
        for i in range(4):
            write_batch(r, ["solo"], t0=i * 1_000_000, n=10, dc="east")
            r.flush()
        r.scan_host()
        assert scanmod.LAST_MERGE_PATH == "concat"
        eng.close()

    def test_disjoint_runs_merge_not_lexsort(self, tmp_path, scan_ab):
        """Multi-series TWCS-style time-disjoint SSTs take the sorted-run
        merge, and its output is bit-exact with forced lexsort."""
        eng, r = make_region(tmp_path)
        for i in range(6):
            write_batch(r, ["h0", "h1", "h2", "h3"], t0=i * 1_000_000, n=40)
            r.flush()
        c0 = REGISTRY.value("greptime_scan_merge_total", ("merge",))
        seq, par = scan_ab(r)
        assert scanmod.LAST_MERGE_PATH == "merge"
        assert REGISTRY.value("greptime_scan_merge_total", ("merge",)) > c0
        assert_same_columns(seq, par)
        eng.close()

    def test_unpackable_keys_take_lexsort(self):
        """Keys too wide for 62 bits fall to the global lexsort, which
        gives the same order as the stable reference."""
        parts = [
            {"ts": np.array([1 << 61, 0], dtype=np.int64),
             "tsid": np.array([3, 3], dtype=np.int64),
             "seq": np.array([0, 1], dtype=np.int64)},
            {"ts": np.array([5], dtype=np.int64),
             "tsid": np.array([1], dtype=np.int64),
             "seq": np.array([2], dtype=np.int64)},
        ]
        got, path = scanmod.merge_parts(parts, "ts", "tsid", "seq")
        assert path == "lexsort"
        assert got["tsid"].tolist() == [1, 3, 3]
        assert got["ts"].tolist() == [5, 0, 1 << 61]

    def test_merge_parts_fuzz_vs_lexsort(self):
        """Random sorted/unsorted parts: every strategy must reproduce
        the stable-lexsort permutation bit-exactly."""
        rng = np.random.default_rng(11)
        for trial in range(25):
            k = int(rng.integers(1, 6))
            parts = []
            for j in range(k):
                n = int(rng.integers(0, 60))
                tsid = rng.integers(0, 5, size=n).astype(np.int64)
                ts = rng.integers(0, 40, size=n).astype(np.int64) * 1000
                seq = np.full(n, j, dtype=np.int64)
                val = rng.standard_normal(n)
                if rng.random() < 0.6 and n:
                    o = np.lexsort((seq, ts, tsid))
                    tsid, ts, seq, val = tsid[o], ts[o], seq[o], val[o]
                parts.append(
                    {"ts": ts, "tsid": tsid, "seq": seq, "val": val})
            ref = {
                key: np.concatenate([p[key] for p in parts])
                for key in ("ts", "tsid", "seq", "val")
            }
            order = np.lexsort((ref["seq"], ref["ts"], ref["tsid"]))
            ref = {key: v[order] for key, v in ref.items()}
            got, path = merge_parts(parts, "ts", "tsid", "seq")
            assert path in ("presorted", "concat", "merge", "packed_sort",
                            "lexsort", "empty")
            assert_same_columns(ref, got)


class TestKnobsAndQuota:
    def test_thread_knob(self, monkeypatch):
        cores = os.cpu_count() or 1
        assert scan_threads(20) == min(8, cores)
        assert scan_threads(3) == min(3, cores)
        assert scan_threads(0) == 1
        # the env knob overrides the default cap entirely
        monkeypatch.setenv("GREPTIME_SCAN_THREADS", "3")
        assert scan_threads(20) == 3
        monkeypatch.setenv("GREPTIME_SCAN_THREADS", "1")
        assert scan_threads(20) == 1

    def test_read_parts_order_and_concurrency(self, monkeypatch):
        monkeypatch.setenv("GREPTIME_SCAN_THREADS", "4")
        names = []

        def task(i):
            def run():
                names.append(threading.current_thread().name)
                time.sleep(0.01)
                return i
            return run

        out = read_parts([task(i) for i in range(8)])
        assert out == list(range(8))  # order-preserving
        assert any(n.startswith("scan-decode") for n in names)

        monkeypatch.setenv("GREPTIME_SCAN_THREADS", "1")
        names.clear()
        out = read_parts([task(i) for i in range(4)])
        assert out == list(range(4))
        assert not any(n.startswith("scan-decode") for n in names)

    def test_quota_reject_falls_back_to_sequential(
        self, tmp_path, monkeypatch
    ):
        eng, r = make_region(tmp_path)
        for i in range(3):
            write_batch(r, ["h0", "h1"], t0=i * 1_000_000, n=20)
            r.flush()
        mem = WorkloadMemoryManager()
        mem.register("scan", 1, usage_fn=scanmod.staging_bytes,
                     policy="reject")
        r.memory = None  # region.write admission must not interfere
        f0 = REGISTRY.value(
            "greptime_scan_sequential_fallbacks_total", ("quota",))
        # pin a parallel-eligible pool width: on a 1-core container the
        # auto width is 1 and the quota path (parallel-only) never runs
        monkeypatch.setenv("GREPTIME_SCAN_THREADS", "2")
        seq_expected = r.scan_host()  # no manager: parallel reference
        r.memory = mem
        out = r.scan_host()
        assert REGISTRY.value(
            "greptime_scan_sequential_fallbacks_total", ("quota",)) > f0
        assert_same_columns(seq_expected, out)
        assert scanmod.staging_bytes() == 0  # fully released
        eng.close()


class TestObjectDecodeGuard:
    def test_hot_path_never_materializes_objects(self, tmp_path):
        """TIER-1 GUARD: the device-cache build (the hot scan path) must
        not decode a single per-row python object for dictionary-encoded
        string columns — tags travel as codes end to end."""
        from greptimedb_tpu.storage.cache import build_device_table

        eng, r = make_region(tmp_path)
        write_batch(r, ["h0", "h1", "h2"], t0=0, n=30)
        r.flush()
        write_batch(r, ["h1", "h3"], t0=60_000, n=10)  # + memtable rows
        c0 = REGISTRY.value("greptime_scan_object_decode_rows_total")
        dt = build_device_table(r)
        assert REGISTRY.value("greptime_scan_object_decode_rows_total") == c0
        # and the coded columns are still correct
        vocab = dt.dicts["hostname"]
        host_codes = np.asarray(dt.columns["hostname"])[
            np.asarray(dt.row_mask)]
        raw = r.scan_host()
        assert np.array_equal(
            np.array([vocab[c] for c in host_codes], dtype=object),
            raw["hostname"],
        )
        # sanity: the RAW scan path does decode objects (counter works)
        assert REGISTRY.value("greptime_scan_object_decode_rows_total") > c0
        eng.close()


class TestCompaction:
    def test_compact_parallel_parity(self, tmp_path, monkeypatch,
                                     ineligible):
        """Compaction through the parallel reader + sorted-run merge
        produces the same merged table as the sequential lexsort path."""
        def build(name):
            eng, r = make_region(tmp_path, name=name)
            write_batch(r, ["h0", "h1", "h2"], t0=0, n=30)
            r.flush()
            write_batch(r, ["h0", "h1", "h2"], t0=0, n=30, val0=50.0)
            r.flush()
            r.delete({"hostname": ["h1"], "dc": ["west"], "ts": [0]})
            r.flush()
            write_batch(r, ["h4"], t0=90_000, n=5)
            r.flush()
            return eng, r

        eng_a, ra = build("a")
        monkeypatch.setenv("GREPTIME_SCAN_THREADS", "1")
        with ineligible("packed_merge"):
            ra.compact()
        monkeypatch.delenv("GREPTIME_SCAN_THREADS")
        eng_b, rb = build("b")
        rb.compact()
        assert len(ra.sst_files) == 1 and len(rb.sst_files) == 1
        assert ra.sst_files[0].num_rows == rb.sst_files[0].num_rows
        assert_same_columns(ra.scan_host(), rb.scan_host())
        eng_a.close()
        eng_b.close()


class TestGridCatchUp:
    def _grid_region(self, tmp_path):
        eng = RegionEngine(
            str(tmp_path / "grid"),
            default_options=RegionOptions(
                wal_enabled=False, flush_threshold_bytes=1 << 40,
                compaction_trigger_files=1 << 30,
            ),
        )
        schema = Schema((
            ColumnSchema("host", T.STRING, S.TAG),
            ColumnSchema("ts", T.TIMESTAMP_MILLISECOND, S.TIMESTAMP),
            ColumnSchema("v", T.FLOAT64, S.FIELD),
        ))
        return eng, eng.create_region(7, schema)

    @staticmethod
    def _write(r, t0, nsteps, hosts=("a", "b")):
        n = nsteps * len(hosts)
        r.write({
            "host": [hosts[i % len(hosts)] for i in range(n)],
            "ts": [t0 + (i // len(hosts)) * 1000 for i in range(n)],
            "v": [float(t0 + i) for i in range(n)],
        })

    def test_flush_catches_up_instead_of_rebuilding(self, tmp_path):
        from greptimedb_tpu.storage.cache import RegionCacheManager
        from greptimedb_tpu.storage.grid import build_grid_table

        eng, r = self._grid_region(tmp_path)
        cache = RegionCacheManager()
        self._write(r, 0, 16)
        r.flush()
        t1 = cache.get_grid(r)
        assert t1 is not None
        # flush of strictly-newer appends: epoch unchanged -> catch up
        self._write(r, 16_000, 16)
        r.flush()
        c0 = REGISTRY.value(
            "greptime_cache_events_total",
            ("region_device", "grid", "catch_up"))
        t2 = cache.get_grid(r)
        assert REGISTRY.value(
            "greptime_cache_events_total",
            ("region_device", "grid", "catch_up")) > c0
        full = build_grid_table(r)
        assert t2.nt == full.nt and t2.step == full.step
        assert np.array_equal(np.asarray(t2.valid), np.asarray(full.valid))
        assert np.array_equal(
            np.asarray(t2.values), np.asarray(full.values))
        # new series in the catch-up delta must refresh the tag matrix
        self._write(r, 32_000, 4, hosts=("a", "b", "c"))
        r.flush()
        t3 = cache.get_grid(r)
        assert t3.num_series == 3
        full3 = build_grid_table(r)
        assert np.array_equal(
            np.asarray(t3.values), np.asarray(full3.values))
        assert np.array_equal(
            np.asarray(t3.tag_codes["host"]),
            np.asarray(full3.tag_codes["host"]))
        eng.close()

    def test_upsert_blocks_catch_up(self, tmp_path):
        from greptimedb_tpu.storage.cache import RegionCacheManager
        from greptimedb_tpu.storage.grid import build_grid_table

        eng, r = self._grid_region(tmp_path)
        cache = RegionCacheManager()
        self._write(r, 0, 16)
        r.flush()
        assert cache.get_grid(r) is not None
        # overwrite an OLD timestamp: content-mutating -> epoch bump
        r.write({"host": ["a"], "ts": [0], "v": [999.0]})
        r.flush()
        c0 = REGISTRY.value(
            "greptime_cache_events_total",
            ("region_device", "grid", "catch_up"))
        t2 = cache.get_grid(r)
        assert REGISTRY.value(
            "greptime_cache_events_total",
            ("region_device", "grid", "catch_up")) == c0  # full rebuild
        full = build_grid_table(r)
        assert np.array_equal(
            np.asarray(t2.values), np.asarray(full.values))
        # the upsert really landed
        vals = np.asarray(t2.values)
        assert 999.0 in vals
        eng.close()


class TestPrefetch:
    def test_s3_prefetch_warms_cache(self, tmp_path):
        from greptimedb_tpu.storage.s3 import MockS3Server, S3ObjectStore

        srv = MockS3Server()
        try:
            cache_dir = str(tmp_path / "s3cache")
            store = S3ObjectStore(
                srv.endpoint, "bkt", cache_dir=cache_dir,
                access_key="k", secret_key="s",
            )
            for i in range(4):
                store.write(f"sst/f{i}.parquet", b"x" * 256)
            # drop the local copies; objects stay remote
            for i in range(4):
                os.unlink(store._cache_path(f"sst/f{i}.parquet"))
            queued = store.prefetch(
                [f"sst/f{i}.parquet" for i in range(4)])
            assert queued == 4
            deadline = time.time() + 5
            paths = [store._cache_path(f"sst/f{i}.parquet")
                     for i in range(4)]
            while time.time() < deadline and not all(
                    os.path.exists(p) for p in paths):
                time.sleep(0.02)
            assert all(os.path.exists(p) for p in paths)
            # already-cached objects are not re-queued
            assert store.prefetch(["sst/f0.parquet"]) == 0
            assert store.read("sst/f1.parquet") == b"x" * 256
        finally:
            srv.stop()

    def test_scan_triggers_readahead(self, tmp_path):
        from greptimedb_tpu.storage.s3 import MockS3Server, S3ObjectStore

        srv = MockS3Server()
        try:
            cache_dir = str(tmp_path / "s3cache2")
            store = S3ObjectStore(
                srv.endpoint, "bkt", cache_dir=cache_dir,
                access_key="k", secret_key="s",
            )
            eng = RegionEngine(
                str(tmp_path / "s3data"), store=store,
                default_options=RegionOptions(
                    wal_enabled=False, flush_threshold_bytes=1 << 40,
                    compaction_trigger_files=1 << 30,
                ),
            )
            r = eng.create_region(3, cpu_schema())
            for i in range(3):
                write_batch(r, ["h0", "h1"], t0=i * 1_000_000, n=10)
                r.flush()
            expected = r.scan_host()
            # cold node: local cache gone, data only in object storage
            import shutil

            shutil.rmtree(cache_dir)
            os.makedirs(cache_dir, exist_ok=True)
            p0 = REGISTRY.value("greptime_scan_files_total", ("prefetched",))
            out = r.scan_host()
            assert REGISTRY.value(
                "greptime_scan_files_total", ("prefetched",)) > p0
            assert_same_columns(expected, out)
            eng.close()
        finally:
            srv.stop()


class TestTelemetry:
    def test_scan_metrics_and_span(self, tmp_path):
        from greptimedb_tpu.utils.tracing import TRACER

        eng, r = make_region(tmp_path)
        for i in range(2):
            write_batch(r, ["h0", "h1"], t0=i * 1_000_000, n=10)
            r.flush()
        reads0 = REGISTRY.value("greptime_scan_files_total", ("read",))
        bytes0 = REGISTRY.value("greptime_scan_bytes_total")
        TRACER.configure(endpoint=None, enabled=True)
        try:
            mark = TRACER.mark()
            r.scan_host(ts_range=(1_000_000, None))
            spans = TRACER.since(mark)
        finally:
            TRACER.disable()
        names = [s["name"] for s in spans]
        assert "scan" in names and "scan_merge" in names
        scan_span = next(s for s in spans if s["name"] == "scan")
        assert scan_span["attributes"]["files"] == 1  # one file pruned
        assert REGISTRY.value(
            "greptime_scan_files_total", ("read",)) == reads0 + 1
        assert REGISTRY.value("greptime_scan_bytes_total") > bytes0
        eng.close()
