"""greptime-lint: the static-analysis framework and its tier-1 gate.

Four surfaces:

- **The gate** — every pass over the whole package must be clean modulo
  the checked-in baseline (each entry justified) and inline
  ``# gl: allow[...]`` comments (reason mandatory).
- **Fixture snippets** — known-bad code must flag with the right code
  and line, known-good must be clean, suppressions must round-trip.
- **The runtime lock-order witness** — catches a seeded ABBA inversion,
  records real acquisition chains from a live db under concurrent load,
  and is ZERO overhead disabled (production never imports it — pinned
  in a subprocess).
- **Fix-forward regressions** — the real defects this round's passes
  found (unguarded metric/workload counter mutations, cross-thread scan
  stat pollution) stay fixed under a thread hammer.
"""

import json
import subprocess
import sys
import threading
import time

import pytest

from greptimedb_tpu.analysis import core
from greptimedb_tpu.analysis.core import (
    analyze_source, apply_baseline, baseline_entries, check_package,
    load_baseline,
)

# ---------------------------------------------------------------------------
# Tier-1 gate: the whole package is clean vs the baseline
# ---------------------------------------------------------------------------


class TestTier1Gate:
    def test_all_passes_clean_over_package(self):
        new, matched, stale, inline = check_package()
        assert not new, "non-baselined findings:\n" + "\n".join(
            f.render() for f in new)
        assert not stale, f"stale baseline entries (prune them): {stale}"

    def test_every_suppression_is_justified(self):
        # baseline entries carry a real reason (the CLI's TODO marker is
        # rejected), and inline allows required one at parse time
        for e in load_baseline():
            assert e.get("reason", "").strip(), f"unjustified: {e}"
            assert not e["reason"].startswith("TODO"), f"unjustified: {e}"
        _new, _matched, _stale, inline = check_package()
        for f in inline:
            assert f.reason.strip(), f.render()

    def test_all_five_pass_families_registered(self):
        names = {p.name for p in core.all_passes()}
        assert names == {"lock_discipline", "lock_order", "hotpath",
                         "durability", "hygiene"}
        codes = {c for p in core.all_passes() for c in p.codes}
        for required in ("GL-L001", "GL-L002", "GL-O001", "GL-O002",
                        "GL-H001", "GL-H002", "GL-D001", "GL-D002",
                        "GL-D003", "GL-T001", "GL-T002", "GL-T003",
                        "GL-K001", "GL-K002"):
            assert required in codes


# ---------------------------------------------------------------------------
# Fixture snippets: known-bad flags, known-good is clean
# ---------------------------------------------------------------------------

LOCK_BAD = '''
import threading

class RegionCacheManager:
    def __init__(self):
        self._struct_lock = threading.RLock()
        self._lru = {}
        self._bytes = 0

    def get(self, key):
        self._lru[key] = 1          # line 11: unguarded write
        with self._struct_lock:
            self._bytes += 8        # guarded: ok
        self._lru.pop(key, None)    # line 14: unguarded mutating call
        return self._lru.get(key)   # read: ok (mode=mutate)
'''

LOCK_GOOD = '''
import threading

class RegionCacheManager:
    def __init__(self):
        self._struct_lock = threading.RLock()
        self._lru = {}
        self._bytes = 0

    def get(self, key):
        with self._struct_lock:
            self._lru[key] = 1
            self._bytes += 8
            self._lru.pop(key, None)
        return self._lru.get(key)
'''

BLOCKING_BAD = '''
import os, threading

class W:
    def __init__(self):
        self._lock = threading.Lock()

    def write(self, fd):
        with self._lock:
            os.fsync(fd)            # line 10: fsync under lock
'''

HOLDS_MARKER = '''
import threading

class Region:
    def __init__(self):
        self._append_log_lock = threading.Lock()
        self._append_log = []
        self._append_base = 0

    def trim(self):
        with self._append_log_lock:
            self._locked_trim()

    def _locked_trim(self):  # gl: holds[_append_log_lock]
        self._append_base += len(self._append_log)
        self._append_log.clear()

    def bad_trim(self):
        self._append_base += 1      # line 19: no lock, no marker
'''

ABBA = '''
import threading

class S:
    def __init__(self):
        self._alock = threading.Lock()
        self._block = threading.Lock()

    def ab(self):
        with self._alock:
            with self._block:
                pass

    def ba(self):
        with self._block:
            with self._alock:
                pass
'''

SELF_ACQUIRE = '''
import threading

class S:
    def __init__(self):
        self._lock = threading.Lock()

    def outer(self):
        with self._lock:
            with self._lock:
                pass
'''

CALL_CYCLE = '''
import threading

class S:
    def __init__(self):
        self._alock = threading.Lock()
        self._block = threading.Lock()

    def helper(self):
        with self._alock:
            pass

    def ab(self):
        with self._alock:
            with self._block:
                pass

    def ba(self):
        with self._block:
            self.helper()
'''

WARM_BAD = '''
import numpy as np

def extend(grid, rows):  # gl: warm-path
    vals = np.asarray(grid.values)      # line 5: host sync
    for i in range(len(rows)):          # line 6: per-row loop
        vals[i] = rows[i]
    return vals.tolist()                # line 8: host sync
'''

WARM_HOST = '''
import numpy as np

def parse(cols, n):  # gl: warm-path(host)
    arr = np.asarray(cols["v"])          # host mode: asarray is fine
    out = [None] * n
    for a, b in zip(cols["a"], cols["b"]):   # line 7: per-row zip
        out.append((a, b))
    for name, col in cols.items():       # O(columns): fine
        _ = col
    return out
'''

WARM_CLOSURE = '''
import jax.numpy as jnp

def build(p):  # gl: warm-path
    scale = float(p.step)        # outer epilogue cast: fine

    def kernel(x, n):
        k = int(n)               # line 8: cast inside kernel closure
        return jnp.sum(x) * k
    return kernel
'''

DUR_BAD = '''
import os

def persist(path, data):
    with open(path + ".tmp", "wb") as f:    # line 5: bare open
        f.write(data)
    os.replace(path + ".tmp", path)          # line 7: no dir fsync
'''

DUR_GOOD = '''
import os
from greptimedb_tpu.storage.object_store import _fsync_dir

def persist(store, path, data):
    store.write(path, data)

def install(tmp, path):
    os.replace(tmp, path)
    _fsync_dir(os.path.dirname(path))
'''

FENCE_BAD = '''
class Manifest:
    def commit(self, action):                # line 3
        self.store.write("delta", b"x")      # line 4: bypasses _write
        self.store.write_if("d2", b"x", if_none_match=True)  # line 5

    def _write(self, path, data):
        self.store.write(path, data)         # owner: clean
'''

FENCE_BAD_WM = '''
import json, os

class SharedLogBroker:
    def set_low_watermark(self, topic, wm):  # line 5
        with open("marker.tmp", "w") as f:   # line 6: bypasses owner
            json.dump(wm, f)

    def _persist_watermarks(self, topic, wm):
        with open("marker.tmp", "w") as f:   # owner: clean
            json.dump(wm, f)
'''

HYGIENE_BAD = '''
from greptimedb_tpu.utils.telemetry import REGISTRY

A = REGISTRY.counter("greptime_x_total", "x", labels=("a",))
B = REGISTRY.counter("greptime_x_total", "x", labels=("b",))
C = REGISTRY.gauge("not_prefixed", "bad name")
D = REGISTRY.histogram("greptime_lat", "h")
E = REGISTRY.counter("greptime_lat_count", "collides with explosion")
'''

KNOB_BAD = '''
import os

UNDOC = os.environ.get("GREPTIME_NOT_A_DOCUMENTED_KNOB", "7")
'''


def codes_at(findings, code):
    return [f.line for f in findings if f.code == code]


class TestLockDisciplineFixtures:
    def test_unguarded_sites_flag_with_lines(self):
        fs = analyze_source(LOCK_BAD, "storage/cache.py",
                            names=["lock_discipline"])
        assert codes_at(fs, "GL-L001") == [11, 14]

    def test_guarded_sites_clean(self):
        assert analyze_source(LOCK_GOOD, "storage/cache.py",
                              names=["lock_discipline"]) == []

    def test_blocking_call_under_lock(self):
        fs = analyze_source(BLOCKING_BAD, "storage/x.py",
                            names=["lock_discipline"])
        assert codes_at(fs, "GL-L002") == [10]

    def test_holds_marker_establishes_lock(self):
        fs = analyze_source(HOLDS_MARKER, "storage/region.py",
                            names=["lock_discipline"])
        assert codes_at(fs, "GL-L001") == [19]

    def test_inline_allow_needs_a_reason(self):
        allowed = BLOCKING_BAD.replace(
            "os.fsync(fd)            # line 10: fsync under lock",
            "os.fsync(fd)  # gl: allow[GL-L002] -- the lock IS the flush serialization")
        assert analyze_source(allowed, "storage/x.py",
                              names=["lock_discipline"]) == []
        reasonless = BLOCKING_BAD.replace(
            "os.fsync(fd)            # line 10: fsync under lock",
            "os.fsync(fd)  # gl: allow[GL-L002]")
        fs = analyze_source(reasonless, "storage/x.py",
                            names=["lock_discipline"])
        assert codes_at(fs, "GL-L002") == [10], \
            "an allow without a reason must not suppress"

    def test_allow_for_other_code_does_not_suppress(self):
        wrong = BLOCKING_BAD.replace(
            "os.fsync(fd)            # line 10: fsync under lock",
            "os.fsync(fd)  # gl: allow[GL-D001] -- wrong code entirely")
        fs = analyze_source(wrong, "storage/x.py",
                            names=["lock_discipline"])
        assert codes_at(fs, "GL-L002") == [10]


class TestLockOrderFixtures:
    def test_abba_cycle_flags(self):
        fs = analyze_source(ABBA, "serving/s.py", names=["lock_order"])
        assert len(codes_at(fs, "GL-O001")) == 1
        assert "_alock" in fs[0].message and "_block" in fs[0].message

    def test_self_acquire_of_plain_lock(self):
        fs = analyze_source(SELF_ACQUIRE, "serving/s.py",
                            names=["lock_order"])
        assert len(codes_at(fs, "GL-O002")) == 1

    def test_rlock_self_acquire_is_fine(self):
        fs = analyze_source(SELF_ACQUIRE.replace("Lock()", "RLock()"),
                            "serving/s.py", names=["lock_order"])
        assert fs == []

    def test_cycle_through_intra_module_call(self):
        fs = analyze_source(CALL_CYCLE, "serving/s.py",
                            names=["lock_order"])
        assert len(codes_at(fs, "GL-O001")) == 1

    def test_consistent_order_clean(self):
        consistent = ABBA.replace(
            "        with self._block:\n            with self._alock:",
            "        with self._alock:\n            with self._block:")
        assert analyze_source(consistent, "serving/s.py",
                              names=["lock_order"]) == []


class TestHotPathFixtures:
    def test_device_warm_flags_syncs_and_loops(self):
        fs = analyze_source(WARM_BAD, "query/x.py", names=["hotpath"])
        assert codes_at(fs, "GL-H001") == [5, 8]
        assert codes_at(fs, "GL-H002") == [6]

    def test_host_mode_flags_only_row_loops(self):
        fs = analyze_source(WARM_HOST, "servers/x.py", names=["hotpath"])
        assert codes_at(fs, "GL-H001") == []
        assert codes_at(fs, "GL-H002") == [7]

    def test_cast_flagged_only_inside_kernel_closures(self):
        fs = analyze_source(WARM_CLOSURE, "query/x.py", names=["hotpath"])
        assert codes_at(fs, "GL-H001") == [8]

    def test_unmarked_function_is_ignored(self):
        unmarked = WARM_BAD.replace("  # gl: warm-path", "")
        assert analyze_source(unmarked, "query/x.py",
                              names=["hotpath"]) == []


class TestDurabilityFixtures:
    def test_bare_open_and_unfsynced_replace(self):
        fs = analyze_source(DUR_BAD, "storage/x.py", names=["durability"])
        assert codes_at(fs, "GL-D001") == [5]
        assert codes_at(fs, "GL-D002") == [7]

    def test_discipline_routed_writes_clean(self):
        assert analyze_source(DUR_GOOD, "storage/x.py",
                              names=["durability"]) == []

    def test_owner_modules_may_open(self):
        fs = analyze_source(DUR_BAD, "storage/wal.py", names=["durability"])
        assert codes_at(fs, "GL-D001") == []  # wal owns the discipline
        assert codes_at(fs, "GL-D002") == [7]  # but still fsyncs renames

    def test_outside_storage_not_in_scope(self):
        assert analyze_source(DUR_BAD, "meta/x.py",
                              names=["durability"]) == []

    def test_fenced_write_bypass_flags_in_manifest(self):
        fs = analyze_source(FENCE_BAD, "storage/manifest.py",
                            names=["durability"])
        assert codes_at(fs, "GL-D003") == [4, 5]

    def test_fenced_write_bypass_flags_watermark_marker(self):
        fs = analyze_source(FENCE_BAD_WM, "storage/remote_wal.py",
                            names=["durability"])
        assert codes_at(fs, "GL-D003") == [6]

    def test_fenced_write_map_only_covers_mapped_files(self):
        # the same shapes in an unmapped storage module are not fenced
        # surfaces (plain ObjectStore writes are GL-D001/2 territory)
        fs = analyze_source(FENCE_BAD, "storage/x.py",
                            names=["durability"])
        assert codes_at(fs, "GL-D003") == []

    def test_current_fenced_surfaces_are_clean(self):
        # baseline-free from day one: the live manifest/broker modules
        # route every fenced-surface write through their owners
        new, _m, _s, _inline = check_package(names=["durability"])
        assert [f for f in new if f.code == "GL-D003"] == []


class TestHygieneFixtures:
    def test_metric_collisions_and_names(self):
        fs = analyze_source(HYGIENE_BAD, "utils/x.py", names=["hygiene"])
        assert codes_at(fs, "GL-T001") == [5]   # label-set mismatch
        assert codes_at(fs, "GL-T002") == [6]   # not greptime_-prefixed
        assert codes_at(fs, "GL-T003") == [7]   # explosion collision

    def test_undocumented_knob_flags(self):
        fs = analyze_source(KNOB_BAD, "utils/x.py", names=["hygiene"])
        assert [f.code for f in fs] == ["GL-K001"]
        assert fs[0].key == "GREPTIME_NOT_A_DOCUMENTED_KNOB"

    def test_runtime_twin_matches_registry(self):
        from greptimedb_tpu.analysis.passes.hygiene import check_registry
        from greptimedb_tpu.utils.telemetry import Registry

        r = Registry()
        r.counter("dup_total")
        r.gauge("dup_total")
        r.counter("BadName")
        r.histogram("greptime_lat")
        r.counter("greptime_lat_count")
        problems = check_registry(r)
        assert any("dup_total" in p for p in problems)
        assert any("BadName" in p for p in problems)
        assert any("greptime_lat_count" in p for p in problems)
        assert check_registry(Registry()) == []


# ---------------------------------------------------------------------------
# Baseline round-trip + stale detection
# ---------------------------------------------------------------------------


class TestBaseline:
    def _findings(self):
        return analyze_source(LOCK_BAD, "storage/cache.py",
                              names=["lock_discipline"])

    def test_round_trip_suppresses_everything(self):
        fs = self._findings()
        entries = baseline_entries(fs)
        new, matched, stale = apply_baseline(self._findings(), entries)
        assert new == [] and stale == []
        assert len(matched) == len(fs)

    def test_reasons_preserved_across_regeneration(self):
        entries = baseline_entries(self._findings())
        for e in entries:
            e["reason"] = "because measured and justified"
        again = baseline_entries(self._findings(), old=entries)
        assert all(e["reason"] == "because measured and justified"
                   for e in again)

    def test_fixed_finding_leaves_stale_entry(self):
        entries = baseline_entries(self._findings())
        fixed = analyze_source(LOCK_GOOD, "storage/cache.py",
                               names=["lock_discipline"])
        new, matched, stale = apply_baseline(fixed, entries)
        assert new == [] and matched == []
        assert len(stale) == len(entries)

    def test_matching_ignores_line_numbers(self):
        entries = baseline_entries(self._findings())
        for e in entries:
            e["line"] = 99999  # cosmetic field only
        new, matched, stale = apply_baseline(self._findings(), entries)
        assert new == [] and stale == []


# ---------------------------------------------------------------------------
# CONFIG.md: generated knob inventory can't drift
# ---------------------------------------------------------------------------


class TestConfigMd:
    def test_checked_in_config_md_is_current(self):
        import os

        from greptimedb_tpu.analysis.passes.hygiene import render_config_md

        path = os.path.join(os.path.dirname(core.package_root()),
                            "CONFIG.md")
        with open(path, encoding="utf-8") as f:
            on_disk = f.read()
        assert on_disk == render_config_md(), (
            "CONFIG.md is stale — regenerate with "
            "`python -m greptimedb_tpu.analysis --write-config`")

    def test_every_knob_read_is_documented(self):
        from greptimedb_tpu.analysis.passes.hygiene import (
            KNOB_DOCS, collect_knob_reads,
        )

        reads = collect_knob_reads(core.load_package())
        undocumented = {k for k, _d, _f, _l in reads} - set(KNOB_DOCS)
        assert not undocumented, undocumented

    @pytest.fixture(scope="class")
    def knobs_read(self):
        from greptimedb_tpu.analysis.passes.hygiene import (
            collect_knob_reads,
        )

        return {k for k, _d, _f, _l in
                collect_knob_reads(core.load_package())}

    # PR 32: each of these selected a second road beside the one every
    # served request takes; the road is now chosen from what the code
    # observes, and the name must not come back as a read or a document
    @pytest.mark.parametrize("knob", [
        "GREPTIME_SCHEDULER", "GREPTIME_SLO", "GREPTIME_PLAN_FUSION",
        "GREPTIME_PROMQL_CACHE", "GREPTIME_GRID", "GREPTIME_LAYOUT_CACHE",
        "GREPTIME_INGEST_VECTOR", "GREPTIME_SCAN_TAG_CODES",
        "GREPTIME_SCAN_FORCE_LEXSORT", "GREPTIME_SCHEDULER_BATCH",
    ])
    def test_removed_switch_stays_removed(self, knobs_read, knob):
        from greptimedb_tpu.analysis.passes.hygiene import KNOB_DOCS

        assert knob not in knobs_read
        assert knob not in KNOB_DOCS


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


class TestCli:
    def test_module_invocation_is_clean(self):
        out = subprocess.run(
            [sys.executable, "-m", "greptimedb_tpu.analysis", "--json"],
            capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stdout + out.stderr
        payload = json.loads(out.stdout)
        assert payload["new"] == []
        assert payload["stale_baseline"] == []

    def test_list_passes(self):
        out = subprocess.run(
            [sys.executable, "-m", "greptimedb_tpu.analysis",
             "--list-passes"], capture_output=True, text=True, timeout=120)
        assert out.returncode == 0
        for name in ("lock_discipline", "lock_order", "hotpath",
                     "durability", "hygiene"):
            assert name in out.stdout


# ---------------------------------------------------------------------------
# Runtime lock-order witness
# ---------------------------------------------------------------------------


class TestWitness:
    def test_seeded_abba_inversion_detected(self):
        from greptimedb_tpu.analysis.witness import Inversion, LockWitness

        w = LockWitness()
        with w.capture():
            a = threading.Lock()
            b = threading.Lock()
        with a:
            with b:
                pass

        def other():
            with b:
                with a:
                    pass

        t = threading.Thread(target=other)
        t.start()
        t.join()
        assert w.inversions, "ABBA inversion not recorded"
        with pytest.raises(Inversion):
            w.check()

    def test_same_creation_line_locks_do_not_alias(self):
        """Instance-level identity: two locks minted on ONE source line
        (or by one constructor line across instances — every Region's
        append-log lock) must keep distinct names, or their mutual ABBA
        self-cancels as a skipped self-edge."""
        from greptimedb_tpu.analysis.witness import Inversion, LockWitness

        w = LockWitness()
        with w.capture():
            a, b = threading.Lock(), threading.Lock()  # same line
        with a:
            with b:
                pass

        def other():
            with b:
                with a:
                    pass

        t = threading.Thread(target=other)
        t.start()
        t.join()
        with pytest.raises(Inversion):
            w.check()

    def test_consistent_order_records_chains_without_inversion(self):
        from greptimedb_tpu.analysis.witness import LockWitness

        w = LockWitness()
        with w.capture():
            a = threading.Lock()
            b = threading.Lock()

        def worker():
            for _ in range(50):
                with a:
                    with b:
                        pass

        ts = [threading.Thread(target=worker) for _ in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert w.inversions == []
        assert len(w.edges) == 1 and len(w.chains) >= 1
        w.check()  # no raise

    def test_rlock_reentrancy_no_self_edge(self):
        from greptimedb_tpu.analysis.witness import LockWitness

        w = LockWitness()
        with w.capture():
            r = threading.RLock()
        with r:
            with r:
                pass
        assert w.edges == {} and w.inversions == []

    def test_condition_interop(self):
        from greptimedb_tpu.analysis.witness import LockWitness

        w = LockWitness()
        with w.capture():
            cond = threading.Condition()
        hit = []

        def waiter():
            with cond:
                while not hit:
                    cond.wait(timeout=5)

        t = threading.Thread(target=waiter)
        t.start()
        time.sleep(0.05)
        with cond:
            hit.append(1)
            cond.notify()
        t.join(timeout=5)
        assert not t.is_alive()
        assert w.inversions == []

    def test_event_and_plain_lock_condition_interop(self):
        """Condition(Lock()) — which Event()/Queue() build internally —
        must work on witnessed PLAIN locks: the wrapper emulates
        CPython's non-RLock fallbacks (_is_owned/_release_save/
        _acquire_restore) instead of delegating to methods a plain
        _thread.lock doesn't have."""
        from greptimedb_tpu.analysis.witness import LockWitness

        w = LockWitness()
        with w.capture():
            ev = threading.Event()
            cond = threading.Condition(threading.Lock())
            import queue

            q = queue.Queue()

        def producer():
            q.put(1)
            with cond:
                cond.notify_all()
            ev.set()

        got = []

        def consumer():
            got.append(q.get(timeout=5))
            ev.wait(timeout=5)

        ts = [threading.Thread(target=consumer),
              threading.Thread(target=producer)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=10)
        assert got == [1] and ev.is_set()
        assert w.inversions == []

    def test_uninstall_restores_stock_factories(self):
        from greptimedb_tpu.analysis import witness as wmod

        w = wmod.LockWitness()
        with w.capture():
            assert threading.Lock is not wmod._ORIG_LOCK
        assert threading.Lock is wmod._ORIG_LOCK
        assert threading.RLock is wmod._ORIG_RLOCK

    @pytest.mark.concurrency
    def test_live_db_under_witness_has_no_inversions(self, tmp_path):
        """Real acquisition chains: a db created under the witness serves
        concurrent ingest + queries; every lock the engine takes is
        witnessed and the recorded order graph must be inversion-free."""
        from greptimedb_tpu.analysis.witness import LockWitness

        w = LockWitness()
        with w.capture():
            from greptimedb_tpu.standalone import GreptimeDB

            db = GreptimeDB()
            db.sql("CREATE TABLE cpu (h STRING, ts TIMESTAMP(3) TIME "
                   "INDEX, v DOUBLE, PRIMARY KEY (h))")
        errors = []

        def ingest(k):
            try:
                for i in range(20):
                    db.sql(f"INSERT INTO cpu VALUES ('h{k}', "
                           f"{1000 + i * 1000 + k}, {float(i)})")
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        def query():
            try:
                for _ in range(10):
                    db.sql("SELECT h, avg(v) FROM cpu GROUP BY h")
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = ([threading.Thread(target=ingest, args=(k,))
                    for k in range(3)]
                   + [threading.Thread(target=query) for _ in range(2)])
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        db.close()
        assert not errors, errors
        assert w.chains, "no acquisition chains recorded — witness dead?"
        w.check()  # any ABBA across engine locks fails here

    def test_zero_overhead_disabled_pin(self):
        """TIER-1 PIN: production code NEVER imports the witness (or the
        analyzer at all) — driving the write+query path in a fresh
        interpreter leaves threading.Lock untouched and the analysis
        package absent from sys.modules.  Disabled cost: exactly zero."""
        code = (
            "import threading\n"
            "orig = threading.Lock\n"
            "from greptimedb_tpu.standalone import GreptimeDB\n"
            "db = GreptimeDB()\n"
            "db.sql(\"CREATE TABLE t (h STRING, ts TIMESTAMP(3) TIME "
            "INDEX, v DOUBLE, PRIMARY KEY (h))\")\n"
            "db.sql(\"INSERT INTO t VALUES ('a', 1000, 1.0)\")\n"
            "r = db.sql('SELECT avg(v) FROM t')\n"
            "assert r.rows == [[1.0]], r.rows\n"
            "db.close()\n"
            "import sys\n"
            "bad = [m for m in sys.modules if m.startswith("
            "'greptimedb_tpu.analysis')]\n"
            "assert not bad, bad\n"
            "assert threading.Lock is orig\n"
            "print('PIN_OK')\n"
        )
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=300,
            env={**__import__("os").environ, "JAX_PLATFORMS": "cpu",
                 "GREPTIME_LOCK_WITNESS": ""},
        )
        assert out.returncode == 0, out.stdout + out.stderr
        assert "PIN_OK" in out.stdout


# ---------------------------------------------------------------------------
# Fix-forward regressions: the defects the passes found stay fixed
# ---------------------------------------------------------------------------


class TestFixForwardRegressions:
    def test_counter_increments_are_atomic(self):
        """GL-L001 fix (utils/telemetry.py): float += on metric children
        is a read-modify-write; unguarded, concurrent scheduler/ingest
        increments lost updates.  8 threads x 5k incs must be exact."""
        from greptimedb_tpu.utils.telemetry import Registry

        r = Registry()
        c = r.counter("hammer_total").labels()
        h = r.histogram("hammer_lat", buckets=(1.0, 2.0)).labels()
        g = r.gauge("hammer_gauge").labels()
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)  # provoke interleaving
        try:
            def work():
                for _ in range(5000):
                    c.inc()
                    h.observe(0.5)
                    g.inc()
            ts = [threading.Thread(target=work) for _ in range(8)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
        finally:
            sys.setswitchinterval(old)
        assert c.value == 8 * 5000
        assert h.total == 8 * 5000
        assert h.counts[0] == 8 * 5000
        assert g.value == 8 * 5000

    def test_workload_counters_are_exact_under_contention(self):
        """GL-L001 fix (utils/memory.py): Workload.rejected/reclaims/
        peak_bytes mutate under the manager lock now — concurrent
        admissions account exactly."""
        from greptimedb_tpu.errors import ResourcesExhausted
        from greptimedb_tpu.utils.memory import WorkloadMemoryManager

        mem = WorkloadMemoryManager()
        reclaimed = []
        mem.register("hammer", 100, usage_fn=lambda: 1000,
                     reclaim_fn=lambda n: reclaimed.append(n),
                     policy="reject")
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            def work():
                for _ in range(2000):
                    with pytest.raises(ResourcesExhausted):
                        mem.admit("hammer", 10)
            ts = [threading.Thread(target=work) for _ in range(8)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
        finally:
            sys.setswitchinterval(old)
        u = mem.usage()["hammer"]
        assert u["rejected"] == 8 * 2000
        assert u["reclaims"] == 8 * 2000
        assert u["peak_bytes"] == 1010

    def test_scan_stats_are_thread_local(self):
        """Cross-thread scan-stat pollution fix (storage/scan.py): a
        compaction/scan on another thread must not overwrite this
        query's cold-phase attribution."""
        from greptimedb_tpu.storage import scan as scanmod

        barrier = threading.Barrier(2, timeout=10)
        results = {}

        def run(tag, nparts):
            tasks = [lambda i=i: {"v": i} for i in range(nparts)]
            barrier.wait()
            scanmod.read_parts(tasks)
            barrier.wait()  # both finished writing before reading
            results[tag] = dict(scanmod.scan_stats())

        t1 = threading.Thread(target=run, args=("a", 3))
        t2 = threading.Thread(target=run, args=("b", 7))
        t1.start(); t2.start()
        t1.join(); t2.join()
        assert results["a"]["files"] == 3
        assert results["b"]["files"] == 7
