#!/usr/bin/env python
"""TSBS-style benchmark: double-groupby-all (the north-star query).

Reference baseline (BASELINE.md): GreptimeDB v0.12.0 on EC2 c5d.2xlarge
runs TSBS `double-groupby-all` — mean of all 10 CPU metrics grouped by
(hostname, hour) over a 12-hour window at scale=4000 — in 1330.05 ms.

This bench builds the same-shape dataset (4000 hosts, 24 h @ 10 s, 10 f64
metric columns ≈ 34.5 M rows), ingests it through the real write path
(tag encode → memtable → Parquet SST), loads it into the device cache, and
measures steady-state SQL latency of the north-star query (median of 10
runs after 2 warmups — the reference's TSBS numbers are warm medians too).

Runs in ONE process on whatever backend JAX gives it; every JSON line
says which (``backend`` = ``jax.devices()[0].platform``, and on the
headline ``device`` = platform, device_kind, count).  Headline line:
  {"metric": "tsbs_double_groupby_all_ms", "value": <median ms>,
   "unit": "ms", "vs_baseline": <value / 1330.05>, "backend": ...,
   "device": {...}}   (lower is better)
The PromQL bench is its own script (bench_promql.py), run by itself.

Env knobs: GREPTIME_BENCH_SCALE (hosts, default 4000),
GREPTIME_BENCH_HOURS (default 24), GREPTIME_BENCH_DATA (cache dir).
"""

from __future__ import annotations

import json
import os
import signal
import sys
import time

import numpy as np

BASELINE_MS = 1330.05
SCALE = int(os.environ.get("GREPTIME_BENCH_SCALE", "4000"))
HOURS = int(os.environ.get("GREPTIME_BENCH_HOURS", "24"))
# Wall-clock budget: the driver kills the bench with `timeout`; emit the
# JSON line from however many runs completed before the budget expires.
# r03's driver run was allowed >1500s of wall clock; 600 gives a cold
# checkout room for generation + grid build + 10 timed runs (SIGTERM
# still emits whatever completed)
BUDGET_S = float(os.environ.get("GREPTIME_BENCH_BUDGET_S", "600"))
START = time.time()
STEP_S = 10
DATA_DIR = os.environ.get(
    "GREPTIME_BENCH_DATA", os.path.join(os.path.dirname(__file__), ".bench_data")
)
METRICS = [
    "usage_user", "usage_system", "usage_idle", "usage_nice", "usage_iowait",
    "usage_irq", "usage_softirq", "usage_steal", "usage_guest",
    "usage_guest_nice",
]
T0 = 1451606400000  # 2016-01-01, the TSBS epoch


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


INGEST_BASELINE_ROWS_S = 326_839.28  # docs/benchmarks/tsbs/v0.12.0.md:15-20
_ingest_rate: list[float] = []  # rows/s, filled by build_db on generation


def _db_dir() -> str:
    # scale-scoped: runs at different scales never share a table
    return os.path.join(DATA_DIR, f"db_{SCALE}_{HOURS}")


def build_db():
    from greptimedb_tpu.standalone import GreptimeDB
    from greptimedb_tpu.storage.region import RegionOptions

    marker = os.path.join(_db_dir(), f"ready_{SCALE}_{HOURS}")
    db = GreptimeDB(
        _db_dir(),
        # hourly flushes into one 24h TWCS window re-merge the whole window
        # every 8 files — O(N^2) rewriting that ate the r02 budget. The
        # bench's TWCS window matches the flush cadence instead.
        region_options=RegionOptions(wal_enabled=False,
                                     flush_threshold_bytes=1 << 40,
                                     compaction_window_ms=3600 * 1000,
                                     compaction_trigger_files=8),
    )
    cols = ", ".join(f"{m} DOUBLE" for m in METRICS)
    db.sql(
        f"CREATE TABLE IF NOT EXISTS cpu (hostname STRING, "
        f"ts TIMESTAMP(3) TIME INDEX, {cols}, PRIMARY KEY (hostname))"
    )
    if os.path.exists(marker):
        return db

    log(f"generating TSBS data: scale={SCALE}, {HOURS}h @ {STEP_S}s ...")
    region = db._region_of("cpu")
    steps_per_hour = 3600 // STEP_S
    hostnames = np.array([f"host_{i}" for i in range(SCALE)], dtype=object)
    rng = np.random.default_rng(7)
    # random-walk per host, ingested in hour-sized chunks (row-major: for
    # each timestep all hosts report, like the TSBS generator). Generation
    # (rng) is excluded from the measured ingest time — TSBS measures the
    # loader's insert rate, not the generator.
    state = rng.uniform(0, 100, size=(SCALE, len(METRICS)))
    ingest_s = 0.0
    t_wall = time.time()
    for hour in range(HOURS):
        n = SCALE * steps_per_hour
        ts = (
            T0
            + (hour * steps_per_hour + np.repeat(np.arange(steps_per_hour), SCALE))
            * STEP_S * 1000
        )
        hosts = np.tile(hostnames, steps_per_hour)
        data = {"hostname": hosts, "ts": ts}
        walk = rng.normal(0, 1, size=(steps_per_hour, SCALE, len(METRICS)))
        series = np.clip(state[None, :, :] + np.cumsum(walk, axis=0), 0, 100)
        state = series[-1]
        for j, m in enumerate(METRICS):
            data[m] = series[:, :, j].reshape(-1)
        t0 = time.time()
        region.write(data)
        region.flush()
        ingest_s += time.time() - t0
        log(f"  hour {hour + 1}/{HOURS} ingested "
            f"({(hour + 1) * n:,} rows, {time.time() - t_wall:.0f}s wall, "
            f"{(hour + 1) * n / max(ingest_s, 1e-9):,.0f} rows/s ingest)")
    rate = HOURS * SCALE * steps_per_hour / max(ingest_s, 1e-9)
    _ingest_rate.append(rate)
    # persist next to the ready marker: a later invocation on cached data
    # and post-generation SIGTERMs still report the rate this build measured
    with open(os.path.join(_db_dir(), "ingest_rate.json"), "w") as f:
        json.dump({"rows_per_s": rate}, f)
    with open(marker, "w") as f:
        f.write("ok")
    return db


_times: list[float] = []
_warmup_times: list[float] = []  # SIGTERM fallback when no timed run finished
_emitted = False
_backend = "unknown"  # jax.devices()[0].platform, set in main()
_device: dict = {}  # platform, kind, count — on the headline line
# derived-layout cache counters (set before emit): the perf trajectory
# must attribute warm-query wins to the bucket-major layout, not guess
_extra_stats: dict = {}


def _headline(times: list[float]) -> str:
    value = float(np.median(times))
    line = {
        "metric": "tsbs_double_groupby_all_ms",
        "value": round(value, 2),
        "unit": "ms",
        "vs_baseline": round(value / BASELINE_MS, 4),
        "backend": _backend,
        "device": _device,
        "runs": len(times),
        "scale": SCALE,
    }
    line.update(_extra_stats)
    if SCALE != 4000:
        # latency scales ~linearly in (series x window) volume on this
        # bandwidth-bound kernel; note it so the number isn't misread
        line["note"] = f"reduced scale {SCALE}/4000; not baseline-comparable"
    return json.dumps(line)


def _ingest_line() -> str | None:
    rate = _ingest_rate[0] if _ingest_rate else None
    if rate is None:
        try:  # measured by an earlier invocation of this same build
            with open(os.path.join(_db_dir(), "ingest_rate.json")) as f:
                rate = float(json.load(f)["rows_per_s"])
        except (OSError, ValueError, KeyError):
            return None
    return json.dumps({
        "metric": "tsbs_ingest_rate",
        "value": round(rate, 1),
        "unit": "rows/s",
        "vs_baseline": round(rate / INGEST_BASELINE_ROWS_S, 4),
        "backend": "host",
    })


def emit(times: list[float]) -> None:
    """Print the JSON line(s) of record from whatever runs completed.
    Headline metric first; the ingest-rate line follows when this run
    generated data (cached data = nothing honest to report)."""
    global _emitted
    if _emitted or not times:
        return
    _emitted = True
    print(_headline(times), flush=True)
    ing = _ingest_line()
    if ing:
        print(ing, flush=True)


def _on_term(signum, frame):
    # async-signal context: the main thread may hold the stdout/stderr
    # BufferedWriter lock, so print() here could raise a reentrancy error —
    # write the JSON line with raw os.write instead
    global _emitted
    times = _times or _warmup_times[-1:]
    if not _emitted:
        os.write(2, f"signal {signum}; emitting from {len(times)} runs\n".encode())
        if times:
            _emitted = True
            os.write(1, (_headline(times) + "\n").encode())
        ing = _ingest_line()  # ingest happened even if no query finished
        if ing:
            os.write(1, (ing + "\n").encode())
    os._exit(0 if _emitted else 1)


def prepare_grid(db) -> None:
    """Materialize the resident grid OUTSIDE any timed section: restore
    the host tensors from the on-disk snapshot when the region matches
    (seconds), else build from the SSTs (the expensive path) and persist
    the snapshot for every later invocation on this data dir."""
    from greptimedb_tpu.storage.grid import (
        load_grid_snapshot, save_grid_snapshot,
    )

    region = db._table_view("cpu")
    snap = os.path.join(_db_dir(), "grid_snap")
    t0 = time.time()
    table = load_grid_snapshot(snap, region, mesh=db.mesh)
    if table is not None:
        db.cache.install_grid(region, table)
        log(f"grid restored from snapshot in {time.time() - t0:.0f}s "
            f"({table.nbytes() / 1e9:.2f} GB resident)")
        return
    log("building resident grid from SSTs ...")
    table, _bounds = db.grid_table("cpu", None)
    if table is None:
        log("WARNING: region ineligible for the dense grid; row path")
        return
    log(f"grid built in {time.time() - t0:.0f}s; persisting snapshot ...")
    try:
        save_grid_snapshot(table, region, snap)
    except OSError as e:
        log(f"snapshot persist failed (non-fatal): {e}")


def cold_scan_bench(db) -> None:
    """Cold-scan A/B (round 10): rebuild the query-ready device table for
    a multi-SST window straight from Parquet — the cold-query/cache-
    rebuild path — once through the streaming scan pipeline (parallel
    decode + code-path tags + sorted-run merge + overlapped upload) and
    once through the sequential reference (GREPTIME_SCAN_THREADS=1 +
    forced lexsort + raw tag decode).  Emits one JSON line with the wall
    clocks, per-phase breakdown and scan counters read from the SAME
    registry /metrics serves, plus a bit-exact parity verdict from a
    smaller window (bounded memory)."""
    import gc

    import greptimedb_tpu.storage.scan as scanmod
    from greptimedb_tpu.storage.cache import build_device_table
    from greptimedb_tpu.utils.telemetry import REGISTRY

    region = db._region_of("cpu")
    nfiles = len(region.sst_files)
    if nfiles < 8:
        log(f"cold-scan bench skipped: only {nfiles} SSTs")
        return
    window_h = min(10, HOURS)
    lo = T0
    hi = T0 + window_h * 3600 * 1000
    seq_env = {
        "GREPTIME_SCAN_THREADS": "1",
        "GREPTIME_SCAN_FORCE_LEXSORT": "1",
        "GREPTIME_SCAN_TAG_CODES": "off",
    }
    # the pipeline leg pins its knobs explicitly ("" = unset-equivalent):
    # ambient operator/debug exports must not silently turn the A/B's
    # fast leg into a second slow leg
    pipe_env = {
        "GREPTIME_SCAN_THREADS": "",
        "GREPTIME_SCAN_FORCE_LEXSORT": "",
        "GREPTIME_SCAN_TAG_CODES": "on",
    }

    def phase_sums() -> dict:
        out: dict = {}
        for name, _kind, _ln, key, child in REGISTRY.snapshot():
            if name == "greptime_scan_phase_seconds":
                out[key[0]] = child.sum
        return out

    def one(env, rng):
        prior = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
        try:
            p0 = phase_sums()
            t0 = time.time()
            table = build_device_table(region, rng)
            ms = (time.time() - t0) * 1000
            p1 = phase_sums()
            ph = {k: round((p1.get(k, 0.0) - p0.get(k, 0.0)) * 1000, 1)
                  for k in p1}
            return table, ms, ph
        finally:
            for k, v in prior.items():  # restore operator exports
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    # untimed warmups: whichever leg runs first must not pay one-time
    # costs the other leg skips — first-touch disk reads (byte sweep)
    # and pyarrow's lazy filtered-read initialization (~1.3 s on the
    # first pq.read_table with filters in a fresh process; small-window
    # build below).  The A/B compares decode+merge+canonicalize+upload.
    small = (lo, lo + 3600 * 1000)
    for m in region.sst_files:
        lp = region.store.local_path(m.path)
        if lp:
            with open(lp, "rb") as f:
                while f.read(1 << 24):
                    pass
    _w, _, _ = one(seq_env, small)
    del _w
    reads0 = REGISTRY.value("greptime_scan_files_total", ("read",))
    # two interleaved rounds per leg, min of each: page-cache/allocator
    # warm-in lands on the first round of BOTH legs instead of biasing
    # whichever ran first.  Pipeline still leads each round, so residual
    # warmup favors the sequential leg — the speedup is a lower bound.
    new_ms = seq_ms = float("inf")
    new_ph: dict = {}
    rows = 0
    merge_path = ""
    files_read = 0
    pipe_obj_rows = 0  # object decodes DURING pipeline legs (pinned 0)
    for _round in range(2):
        obj0 = REGISTRY.value("greptime_scan_object_decode_rows_total")
        table, ms, ph = one(pipe_env, (lo, hi))
        pipe_obj_rows += int(
            REGISTRY.value("greptime_scan_object_decode_rows_total") - obj0)
        if ms < new_ms:
            new_ms, new_ph = ms, ph
            merge_path = scanmod.LAST_MERGE_PATH
        if not rows:
            rows = int(np.asarray(table.row_mask).sum())
            files_read = int(REGISTRY.value(
                "greptime_scan_files_total", ("read",)) - reads0)
        del table
        gc.collect()
        _t, ms, _ph = one(seq_env, (lo, hi))
        seq_ms = min(seq_ms, ms)
        del _t
        gc.collect()

    # parity on a bounded window (both tables resident at once)
    pt, _, _ = one(pipe_env, small)
    st, _, _ = one(seq_env, small)
    parity = "ok"
    for name in pt.columns:
        a = np.asarray(pt.columns[name])
        b = np.asarray(st.columns[name])
        if not np.array_equal(a, b, equal_nan=a.dtype.kind == "f"):
            parity = f"MISMATCH:{name}"
            break
    if pt.dicts != st.dicts:
        parity = "MISMATCH:dicts"
    del pt, st
    gc.collect()

    print(json.dumps({
        "metric": "scan_ms_cold",
        "value": round(new_ms, 1),
        "unit": "ms",
        "scan_ms_cold_seq": round(seq_ms, 1),
        "speedup": round(seq_ms / max(new_ms, 1e-9), 2),
        "files": files_read,
        "rows": rows,
        "merge_path": merge_path,
        "phases_ms": new_ph,
        "scan_threads": scanmod.scan_threads(files_read),
        "scan_rows_total": int(
            REGISTRY.value("greptime_scan_rows_total")),
        "object_decode_rows": pipe_obj_rows,
        "parity": parity,
        "backend": _backend,
    }), flush=True)


def scrub_bench(db, sql) -> None:
    """Scrubber overhead A/B (round 19 acceptance d): warm query medians
    with the background integrity scrubber enabled at PRODUCTION pacing
    (one completed sweep, then interval-gated no-op ticks — the steady
    state a serving node lives in) vs off, plus the disclosed
    during-sweep worst case (a sweep actively verifying multi-MB SSTs
    competes for the container's cores until preemption or the next
    interval gate)."""
    import statistics
    import threading

    from greptimedb_tpu.storage.scrubber import Scrubber

    def median_ms(n=11):
        times = []
        for _ in range(n):
            t0 = time.time()
            db.sql(sql)
            times.append((time.time() - t0) * 1000)
        return statistics.median(times)

    def with_ticker(scrub, fn):
        stop = threading.Event()

        def ticker():
            # the production schedule: one bounded batch per idle tick
            # at the scheduler's 50ms cadence (serving/scheduler.py)
            while not stop.is_set():
                scrub.tick()
                stop.wait(0.05)

        t = threading.Thread(target=ticker, daemon=True)
        t.start()
        try:
            return fn()
        finally:
            stop.set()
            t.join(timeout=30)

    # the bench owns scrub scheduling: the instance's auto-armed
    # scrubber (standalone.py) must not tick during the OFF baseline
    # (warmup's kick_idle may have started the worker pool)
    if getattr(db, "scheduler", None) is not None:
        db.scheduler.idle_hook = None
    off_ms = median_ms()
    # acceptance leg — production steady state: default pacing (a
    # completed sweep, then GREPTIME_SCRUB_INTERVAL_S of gated no-op
    # ticks); must be within noise of off
    scrub = Scrubber(db.regions)
    scrub._resume_skip = 0  # a partial auto-sweep's cursor would skip items
    scrub.run_sweep()  # untimed; the next sweep gates 300s away
    steady_ms = with_ticker(scrub, median_ms)
    # during-sweep worst case, disclosed: continuous verify competing
    # for cores (production sees this for one sweep per interval, and
    # interactive pressure through the scheduler preempts it)
    active = Scrubber(db.regions, interval_s=0, batch=4)
    active._resume_skip = 0
    active_ms = with_ticker(active, median_ms)
    print(json.dumps({
        "metric": "scrub_overhead",
        "warm_ms_scrub_off": round(off_ms, 1),
        "warm_ms_scrub_on": round(steady_ms, 1),
        "ratio": round(steady_ms / max(off_ms, 1e-9), 3),
        "warm_ms_mid_sweep": round(active_ms, 1),
        "mid_sweep_ratio": round(active_ms / max(off_ms, 1e-9), 3),
        "sweeps": scrub.sweeps + active.sweeps,
        "items_verified": scrub.items + active.items,
        "corrupt_found": scrub.corrupt + active.corrupt,
        "backend": _backend,
    }), flush=True)


def main() -> None:
    import jax

    signal.signal(signal.SIGTERM, _on_term)
    signal.signal(signal.SIGINT, _on_term)

    # one process, on whatever backend JAX gives it; the JSON says which
    from greptimedb_tpu.compile.xla_cache import configure_xla_cache

    global _backend
    devs = jax.devices()
    _backend = devs[0].platform
    _device.update(platform=_backend, kind=devs[0].device_kind,
                   count=len(devs))
    log(f"jax devices: {devs}; compile cache: {configure_xla_cache()}")

    db = build_db()
    prepare_grid(db)

    # TSBS double-groupby-all: avg of all 10 metrics by (hostname, hour)
    # over a 12h window (window shrinks with GREPTIME_BENCH_HOURS)
    window_h = min(12, HOURS)
    q_start = T0 + ((HOURS - window_h) // 2) * 3600 * 1000
    q_end = q_start + window_h * 3600 * 1000
    aggs = ", ".join(f"avg({m})" for m in METRICS)
    sql = (
        f"SELECT hostname, date_trunc('hour', ts) AS hour, {aggs} "
        f"FROM cpu WHERE ts >= {q_start} AND ts < {q_end} "
        f"GROUP BY hostname, hour"
    )

    log("warmup (compile) ...")
    t0 = time.time()
    r = db.sql(sql)
    first_ms = (time.time() - t0) * 1000
    _warmup_times.append(first_ms)
    log(f"  first run: {first_ms:.0f} ms, {r.num_rows} groups")
    expected_groups = SCALE * window_h
    assert r.num_rows == expected_groups, (r.num_rows, expected_groups)

    deadline = START + BUDGET_S
    second_ms = first_ms
    if time.time() < deadline or first_ms < 30_000:
        t0 = time.time()
        db.sql(sql)
        second_ms = (time.time() - t0) * 1000
        _warmup_times.append(second_ms)
        log(f"  second run: {second_ms:.0f} ms")

    # the 10-run warm median is the number of record (round-3 verdict
    # item #2): when each run is affordable, run all 10 regardless of
    # the soft budget — the overshoot is bounded (hard cap below);
    # only genuinely slow runs degrade to however many fit.
    hard_cap = deadline + 300
    while len(_times) < 10:
        now = time.time()
        # estimate from the slowest recent run, not just the warm-up:
        # an evicted grid mid-loop must tighten the overshoot bound
        est_ms = max(second_ms, _times[-1] if _times else 0.0)
        affordable = now + est_ms / 1000 < deadline or (
            est_ms < 30_000 and now + est_ms / 1000 < hard_cap
        )
        if not affordable:
            break
        t0 = time.time()
        r = db.sql(sql)
        _times.append((time.time() - t0) * 1000)

    if not _times:
        # budget exhausted during warmup: the warm(er) run is the number
        _times.append(second_ms)
    log(f"runs: {[f'{t:.0f}' for t in _times]} ms; groups={r.num_rows} "
        f"({time.time() - START:.0f}s elapsed)")
    try:
        # counters come from the telemetry registry — the same numbers
        # /metrics serves — so the bench JSON and a scrape can never
        # disagree (the caches mirror every event into the registry)
        from greptimedb_tpu.utils.telemetry import REGISTRY

        _extra_stats["layout_cache_hits"] = int(REGISTRY.value(
            "greptime_cache_events_total", ("layout", "layout", "hit")))
        _extra_stats["layout_cache_builds"] = int(REGISTRY.value(
            "greptime_cache_events_total", ("layout", "layout", "build")))
        # per-workload quota pressure: the registry mirror of
        # utils/memory.py's rejected counters
        _extra_stats["memory_rejects"] = {
            name: int(REGISTRY.value(
                "greptime_memory_admissions_rejected_total", (name,)))
            for name in db.memory.usage()
            if REGISTRY.value(
                "greptime_memory_admissions_rejected_total", (name,))
        }
    except Exception as e:  # noqa: BLE001 — stats are best-effort
        log(f"layout-cache stats unavailable: {e}")
    emit(_times)
    # cold-scan A/B (round 10): cheap next to the warm loop; gated on
    # leftover budget
    if (not os.environ.get("GREPTIME_BENCH_NO_SCAN")
            and deadline - time.time() > 120):
        try:
            cold_scan_bench(db)
        except Exception as e:  # noqa: BLE001 — headline already emitted
            log(f"cold-scan bench skipped: {e!r}")
    # scrubber overhead A/B (round 19): warm medians with the verified
    # background sweep hammering vs idle — cheap (reuses the warm query)
    if (not os.environ.get("GREPTIME_BENCH_NO_SCRUB")
            and deadline - time.time() > 60):
        try:
            scrub_bench(db, sql)
        except Exception as e:  # noqa: BLE001 — headline already emitted
            log(f"scrub bench skipped: {e!r}")
    db.close()

if __name__ == "__main__":
    main()
