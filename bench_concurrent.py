#!/usr/bin/env python
"""Closed-loop N-client concurrency benchmark for the serving scheduler.

Drives the warm TSBS double-groupby shape (hostname × hour over rolling
bucket-aligned windows) from N closed-loop clients submitting through
the query scheduler (serving/), and reports aggregate throughput,
per-request latency percentiles, and the scheduler's batching/admission
counters — read from the PR 3 telemetry registry, the same numbers
/metrics serves, so this bench and a scrape can never disagree.

Prints ONE json line:
  {"metric": "concurrent_throughput_qps", "value": <N-client qps>,
   "clients": N, "single_client_qps": ..., "speedup": ...,
   "p50_ms": ..., "p95_ms": ..., "p99_ms": ...,
   "batches": ..., "batched_queries": ..., "batch_size_obs": ...,
   "largest_batch": ..., "batch_parity_ok": true, "backend": ...}

Env knobs: GREPTIME_BENCH_SCALE (hosts, default 256),
GREPTIME_BENCH_HOURS (default 3), GREPTIME_BENCH_CLIENTS (default 8),
GREPTIME_BENCH_DURATION_S (per closed-loop phase, default 8).
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np

SCALE = int(os.environ.get("GREPTIME_BENCH_SCALE", "256"))
HOURS = int(os.environ.get("GREPTIME_BENCH_HOURS", "3"))
CLIENTS = int(os.environ.get("GREPTIME_BENCH_CLIENTS", "8"))
DURATION_S = float(os.environ.get("GREPTIME_BENCH_DURATION_S", "8"))
STEP_MS = 10_000
T0 = 1451606400000  # TSBS epoch
METRICS = [
    "usage_user", "usage_system", "usage_idle", "usage_nice",
    "usage_iowait", "usage_irq", "usage_softirq", "usage_steal",
    "usage_guest", "usage_guest_nice",
]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def build_db():
    from greptimedb_tpu.standalone import GreptimeDB

    db = GreptimeDB()
    cols = ", ".join(f"{m} DOUBLE" for m in METRICS)
    db.sql(
        f"CREATE TABLE cpu (hostname STRING, ts TIMESTAMP(3) TIME INDEX, "
        f"{cols}, PRIMARY KEY (hostname))"
    )
    rng = np.random.default_rng(42)
    samples = HOURS * 3600_000 // STEP_MS
    t_build = time.time()
    vals = rng.uniform(0.0, 100.0, size=(SCALE, samples, len(METRICS)))
    rows = []
    for h in range(SCALE):
        host = f"host_{h}"
        for i in range(samples):
            cells = ", ".join(f"{vals[h, i, j]:.3f}"
                              for j in range(len(METRICS)))
            rows.append(f"('{host}', {T0 + i * STEP_MS}, {cells})")
    for c in range(0, len(rows), 1000):
        db.sql("INSERT INTO cpu VALUES " + ",".join(rows[c:c + 1000]))
    log(f"ingested {len(rows)} rows x {len(METRICS)} metrics "
        f"({time.time() - t_build:.0f}s)")
    return db


def window_sql(hour_lo: int, hours: int = 1) -> str:
    lo = T0 + hour_lo * 3600_000
    hi = lo + hours * 3600_000
    aggs = ", ".join(f"avg({m})" for m in METRICS)
    return (
        f"SELECT hostname, date_trunc('hour', ts) AS hour, {aggs} "
        f"FROM cpu WHERE ts >= {lo} AND ts < {hi} "
        f"GROUP BY hostname, hour"
    )


def closed_loop(db, n_clients: int, duration_s: float):
    """N closed-loop clients cycling over the rolling windows; returns
    (total_queries, wall_s, latencies_ms)."""
    sched = db.scheduler
    stop_at = time.perf_counter() + duration_s
    lat_ms: list[list[float]] = [[] for _ in range(n_clients)]
    errors: list = []

    def client(ci: int):
        i = ci
        while time.perf_counter() < stop_at:
            q = window_sql(i % HOURS)
            t0 = time.perf_counter()
            try:
                sched.submit(q, tenant=f"client_{ci % 4}")
            except Exception as e:  # noqa: BLE001
                errors.append(e)
                return
            lat_ms[ci].append((time.perf_counter() - t0) * 1000)
            i += 1

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(ci,))
               for ci in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if errors:
        raise errors[0]
    flat = [v for lane in lat_ms for v in lane]
    return len(flat), wall, flat


def pct(xs, p):
    if not xs:
        return None
    return float(np.percentile(np.asarray(xs), p))


def main() -> None:
    import jax

    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    from greptimedb_tpu.compile.xla_cache import configure_xla_cache

    configure_xla_cache()
    from greptimedb_tpu.utils.telemetry import REGISTRY

    db = build_db()
    sched = db.scheduler
    assert sched is not None, (
        "bench_concurrent needs the scheduler (GREPTIME_SCHEDULER!=off)")

    # warm every window class solo (compile + layout cache build)
    log("warming window classes ...")
    solo = {}
    for w in range(HOURS):
        t0 = time.perf_counter()
        solo[w] = db.sql(window_sql(w))
        log(f"  window {w}: first {1000 * (time.perf_counter() - t0):.0f} ms,"
            f" {solo[w].num_rows} groups")
    warm_ms = []
    for _ in range(5):
        t0 = time.perf_counter()
        db.sql(window_sql(0))
        warm_ms.append((time.perf_counter() - t0) * 1000)
    warm_direct_ms = float(np.median(warm_ms))
    log(f"warm solo median (direct db.sql, scheduler bypassed): "
        f"{warm_direct_ms:.1f} ms")

    # batched-vs-solo parity: the stacked dispatch must be bit-exact
    from greptimedb_tpu.query.parser import parse_sql

    sels = [parse_sql(window_sql(w % HOURS))[0] for w in range(4)]
    batched = db.engine.execute_select_batch(sels)
    parity = batched is not None and all(
        b.rows == solo[w % HOURS].rows for w, b in enumerate(batched)
    )
    log(f"stacked-dispatch parity vs solo: {'OK' if parity else 'MISMATCH'}")

    # pre-compile the stacked kernel's pow2 batch classes so XLA builds
    # land in warmup, not inside the timed closed loop (the solo path got
    # the same courtesy above; a production node gets it from traffic)
    for size in (2, 4, 8, 16):
        if size > max(2, CLIENTS * 2):
            break
        t0 = time.perf_counter()
        db.engine.execute_select_batch(
            [parse_sql(window_sql(w % HOURS))[0] for w in range(size)])
        log(f"  stacked kernel class n<={size}: "
            f"{1000 * (time.perf_counter() - t0):.0f} ms")

    # phase A: single-client closed loop through the scheduler
    log(f"phase A: 1 client x {DURATION_S}s ...")
    n1, wall1, lat1 = closed_loop(db, 1, DURATION_S)
    qps1 = n1 / wall1
    log(f"  {n1} queries in {wall1:.1f}s = {qps1:.1f} qps "
        f"(p50 {pct(lat1, 50):.1f} ms)")

    # phase B: N clients closed loop
    b_batches0 = REGISTRY.value("greptime_scheduler_batches_total",
                                ("dispatched",))
    b_queries0 = REGISTRY.value("greptime_scheduler_batched_queries_total")
    b_obs0 = REGISTRY.value("greptime_scheduler_batch_size")
    log(f"phase B: {CLIENTS} clients x {DURATION_S}s ...")
    nN, wallN, latN = closed_loop(db, CLIENTS, DURATION_S)
    qpsN = nN / wallN
    batches = int(REGISTRY.value("greptime_scheduler_batches_total",
                                 ("dispatched",)) - b_batches0)
    batched_queries = int(REGISTRY.value(
        "greptime_scheduler_batched_queries_total") - b_queries0)
    batch_obs = int(REGISTRY.value("greptime_scheduler_batch_size") - b_obs0)
    log(f"  {nN} queries in {wallN:.1f}s = {qpsN:.1f} qps; "
        f"{batches} stacked dispatches served {batched_queries} queries "
        f"(largest {sched.largest_batch})")

    line = {
        "metric": "concurrent_throughput_qps",
        "value": round(qpsN, 2),
        "unit": "queries/s",
        "clients": CLIENTS,
        "single_client_qps": round(qps1, 2),
        "speedup": round(qpsN / qps1, 3) if qps1 else None,
        "p50_ms": round(pct(latN, 50), 2),
        "p95_ms": round(pct(latN, 95), 2),
        "p99_ms": round(pct(latN, 99), 2),
        "queries": nN,
        "warm_solo_direct_ms": round(warm_direct_ms, 2),
        "batches": batches,
        "batched_queries": batched_queries,
        "batch_size_obs": batch_obs,
        "largest_batch": sched.largest_batch,
        "batch_parity_ok": bool(parity),
        "admission_rejected": int(sum(
            REGISTRY.value("greptime_scheduler_rejected_total", (t, r))
            for t in [f"client_{i}" for i in range(4)] + ["default"]
            for r in ("rate", "memory", "concurrency", "queue_full"))),
        "scale": SCALE,
        "hours": HOURS,
        "backend": jax.default_backend(),
    }
    print(json.dumps(line))
    db.close()


if __name__ == "__main__":
    main()
