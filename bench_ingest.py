#!/usr/bin/env python
"""Closed-loop multi-writer ingest benchmark for the vectorized
wire→device pipeline (servers/protocols.py + storage/wal.py group commit
+ sharded memtable appends + hot-tail grid catch-up).

Two wire formats through the REAL server write path (parse →
``_ingest_columns`` → region write: WAL append, memtable, hot-tail
append log), each from closed-loop writer threads:

- **Arrow IPC bulk** (``/v1/arrow/write`` — the standalone surface of
  the in-cluster Flight do_put plane, how the reference's TSBS loader
  ingests): columnar on the wire, zero per-row decode.  This is the
  headline ``ingest_rows_per_s``.
- **InfluxDB line protocol**: text wire, vectorized CSV-transform
  decode (``influx_rows_per_s``).

Both repeat with ``GREPTIME_INGEST_VECTOR=off`` so the A/B line proves
the win comes from the vectorized path (off = the seed's row-object
decode).  A final sustained mixed phase keeps bulk writers running
while warm window-aggregation queries execute, pinning that ingest does
not move warm query medians.  Pipeline counters are read from the PR 3
telemetry registry — the same numbers /metrics serves.

Prints ONE json line:
  {"metric": "ingest_rows_per_s", "value": <best aggregate rows/s>,
   "writers_best": ..., "bulk_1w_rows_per_s": ..., ...,
   "legacy_rows_per_s": ..., "speedup_vs_legacy": ...,
   "influx_rows_per_s": ..., "influx_legacy_rows_per_s": ...,
   "object_decode_rows": 0, "wal_flushes": ...,
   "warm_query_solo_ms": ..., "warm_query_mixed_ms": ...,
   "mixed_ingest_rows_per_s": ..., "backend": ...}

Env knobs: GREPTIME_BENCH_WRITERS (default 2 — GIL-bound decode leaves
little beyond 2 on small hosts), GREPTIME_BENCH_HOSTS (series per
table, default 100), GREPTIME_BENCH_BULK_LINES (rows per bulk body,
default 50000), GREPTIME_BENCH_LINES (rows per line-protocol body,
default 10000), GREPTIME_BENCH_ROWS (rows per writer per phase,
default 2_000_000 bulk / a tenth of that for influx),
GREPTIME_BENCH_WAL_SYNC (fsync per commit group, default off — the
server default), GREPTIME_BENCH_MIXED_S (mixed phase, default 6).
"""

from __future__ import annotations

import io
import json
import os
import sys
import threading
import time

import numpy as np

WRITERS = int(os.environ.get("GREPTIME_BENCH_WRITERS", "2"))
HOSTS = int(os.environ.get("GREPTIME_BENCH_HOSTS", "100"))
BULK_LINES = int(os.environ.get("GREPTIME_BENCH_BULK_LINES", "50000"))
LINES = int(os.environ.get("GREPTIME_BENCH_LINES", "10000"))
ROWS = int(os.environ.get("GREPTIME_BENCH_ROWS", "2000000"))
WAL_SYNC = os.environ.get("GREPTIME_BENCH_WAL_SYNC", "off").lower() in (
    "on", "1", "true")
MIXED_S = float(os.environ.get("GREPTIME_BENCH_MIXED_S", "6"))
STEP_MS = 10_000
T0 = 1451606400000  # TSBS epoch
METRICS = [
    "usage_user", "usage_system", "usage_idle", "usage_nice",
    "usage_iowait", "usage_irq", "usage_softirq", "usage_steal",
    "usage_guest", "usage_guest_nice",
]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def make_lp_body(table: str, n_steps: int, t0_ms: int,
                 rng: np.random.Generator) -> bytes:
    """Line-protocol body: ``HOSTS * n_steps`` rows of the TSBS cpu
    shape (1 tag, 10 float fields, ns timestamps), time-ordered so the
    write path stays pure-append (hot-tail eligible)."""
    vals = rng.uniform(0.0, 100.0, size=(n_steps, HOSTS, len(METRICS)))
    lines = []
    for i in range(n_steps):
        ts = (t0_ms + i * STEP_MS) * 1_000_000
        for h in range(HOSTS):
            fields = ",".join(
                f"{m}={vals[i, h, j]:.3f}" for j, m in enumerate(METRICS))
            lines.append(f"{table},hostname=host_{h} {fields} {ts}")
    return ("\n".join(lines)).encode()


def make_bulk_body(n_steps: int, t0_ms: int,
                   rng: np.random.Generator) -> bytes:
    """Arrow IPC body, same data model: dictionary-coded hostname tag,
    int64 ms ``ts``, 10 float64 fields."""
    import pyarrow as pa

    n = HOSTS * n_steps
    hosts = np.array([f"host_{h}" for h in range(HOSTS)], dtype=object)
    cols = {
        "hostname": pa.array(np.tile(hosts, n_steps)).dictionary_encode(),
        "ts": pa.array(np.repeat(
            t0_ms + np.arange(n_steps, dtype=np.int64) * STEP_MS, HOSTS)),
    }
    for m in METRICS:
        cols[m] = pa.array(rng.uniform(0.0, 100.0, size=n))
    t = pa.table(cols)
    sink = io.BytesIO()
    with pa.ipc.new_stream(sink, t.schema) as w:
        w.write_table(t)
    return sink.getvalue()


class Clock:
    """Strictly advancing epoch so no body ever rewrites an existing
    (series, ts) key — every write stays a pure hot-tail append."""

    def __init__(self):
        self.ms = T0

    def take(self, n_steps: int) -> int:
        t = self.ms
        self.ms += (n_steps + 5) * STEP_MS
        return t


CLOCK = Clock()
RNG = np.random.default_rng(42)


def gen_pools(kind: str, n_writers: int, rows_per_writer: int, tables):
    """Per-writer pre-generated body pools (generation excluded from the
    timed loops, like bench.py's TSBS ingest)."""
    steps = ((BULK_LINES if kind == "bulk" else LINES) + HOSTS - 1) // HOSTS
    rows_per_body = steps * HOSTS
    bodies = max(1, rows_per_writer // rows_per_body)
    pools = []
    for w in range(n_writers):
        pool = []
        for _ in range(bodies):
            t0_ms = CLOCK.take(steps)
            pool.append(make_bulk_body(steps, t0_ms, RNG) if kind == "bulk"
                        else make_lp_body(tables[w], steps, t0_ms, RNG))
        pools.append(pool)
    return pools, rows_per_body


def run_writers(db, kind: str, pools, tables, rows_per_body: int):
    """Each writer drains its pool through the real server ingest path;
    returns (total_rows, wall_s, wire_bytes)."""
    from greptimedb_tpu.servers.http import _ingest_columns
    from greptimedb_tpu.servers.protocols import (parse_arrow_bulk,
                                                  parse_line_protocol)

    errors: list = []

    def writer(w: int):
        try:
            for body in pools[w]:
                if kind == "bulk":
                    _ingest_columns(db, tables[w], parse_arrow_bulk(body))
                else:
                    for table, cols in parse_line_protocol(body).items():
                        _ingest_columns(db, table, cols)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    t0 = time.perf_counter()
    if len(pools) == 1:
        writer(0)
    else:
        threads = [threading.Thread(target=writer, args=(w,))
                   for w in range(len(pools))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    wall = time.perf_counter() - t0
    if errors:
        raise errors[0]
    rows = sum(len(p) for p in pools) * rows_per_body
    wire = sum(len(b) for p in pools for b in p)
    return rows, wall, wire


def phase(db, kind: str, n_writers: int, rows_per_writer: int, label: str):
    tables = [f"{kind}_{label}_w{w}" for w in range(n_writers)]
    # table create + first-batch compile outside the timed loop; the warm
    # bodies take the EARLIER epoch so the timed loop stays time-forward
    # (pure hot-tail appends)
    warm_pools, rpb = gen_pools(kind, n_writers, 1, tables)
    pools, _ = gen_pools(kind, n_writers, rows_per_writer, tables)
    run_writers(db, kind, warm_pools, tables, rpb)
    rows, wall, wire = run_writers(db, kind, pools, tables, rpb)
    rate = rows / wall
    log(f"  {label}: {n_writers}w x {rows // n_writers} rows -> "
        f"{rate:,.0f} rows/s ({wire / wall / 1e6:,.0f} MB/s wire, "
        f"{wall:.2f}s)")
    return rate, tables


def window_sql(table: str, lo_ms: int) -> str:
    hi = lo_ms + 3600_000
    aggs = ", ".join(f"avg({m})" for m in METRICS)
    return (
        f"SELECT hostname, date_trunc('hour', ts) AS hour, {aggs} "
        f"FROM {table} WHERE ts >= {lo_ms} AND ts < {hi} "
        f"GROUP BY hostname, hour"
    )


def main() -> None:
    import jax

    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    from greptimedb_tpu.compile.xla_cache import configure_xla_cache

    configure_xla_cache()
    backend = jax.devices()[0].platform

    import tempfile

    from greptimedb_tpu.standalone import GreptimeDB
    from greptimedb_tpu.storage.region import RegionOptions
    from greptimedb_tpu.utils.telemetry import REGISTRY

    os.environ.pop("GREPTIME_INGEST_VECTOR", None)  # vectorized = default
    tmp = tempfile.TemporaryDirectory(prefix="bench_ingest_")
    db = GreptimeDB(data_home=tmp.name,
                    region_options=RegionOptions(wal_sync=WAL_SYNC))
    log(f"data_home={tmp.name} wal_sync={WAL_SYNC} writers={WRITERS} "
        f"hosts={HOSTS} bulk_lines={BULK_LINES} lp_lines={LINES} "
        f"rows/writer={ROWS}")

    def objdec() -> float:
        return (REGISTRY.value("greptime_ingest_object_decode_rows_total",
                               ("arrow",))
                + REGISTRY.value("greptime_ingest_object_decode_rows_total",
                                ("influxdb",)))

    dec0 = objdec()
    flushes0 = REGISTRY.value("greptime_ingest_wal_batch_size")

    # ---- Arrow IPC bulk (headline) ----
    log("bulk (arrow ipc), vectorized:")
    bulk_1w, q_tables = phase(db, "bulk", 1, ROWS, "solo")
    bulk_nw, _ = phase(db, "bulk", WRITERS, ROWS // WRITERS, "multi")
    vec_decode = objdec() - dec0
    wal_flushes = int(REGISTRY.value("greptime_ingest_wal_batch_size")
                      - flushes0)
    log(f"  object-decode rows on the vectorized paths: {vec_decode:.0f} "
        f"(must be 0); wal flushes {wal_flushes}")

    # ---- InfluxDB line protocol ----
    log("influxdb line protocol, vectorized:")
    influx_nw, _ = phase(db, "influx", WRITERS, ROWS // (10 * WRITERS),
                         "multi")
    vec_decode = objdec() - dec0

    # ---- legacy A/B (GREPTIME_INGEST_VECTOR=off) ----
    os.environ["GREPTIME_INGEST_VECTOR"] = "off"
    try:
        log("legacy row-object decode (GREPTIME_INGEST_VECTOR=off):")
        legacy_bulk, _ = phase(db, "bulk", WRITERS, ROWS // (20 * WRITERS),
                               "legacy")
        legacy_influx, _ = phase(db, "influx", WRITERS,
                                 ROWS // (100 * WRITERS), "legacy")
    finally:
        os.environ.pop("GREPTIME_INGEST_VECTOR", None)

    # ---- sustained mixed read/write ----
    q_table = q_tables[0]
    log("mixed phase: warming query ...")
    q_lo = T0  # first bulk-solo body's window
    db.sql(window_sql(q_table, q_lo))
    solo_ms = []
    for _ in range(7):
        t0 = time.perf_counter()
        db.sql(window_sql(q_table, q_lo))
        solo_ms.append((time.perf_counter() - t0) * 1000)
    warm_solo = float(np.median(solo_ms))
    log(f"  warm solo median {warm_solo:.1f} ms")

    stop = threading.Event()
    mixed_rows = [0]
    mix_tables = [f"bulk_mix_w{w}" for w in range(WRITERS)]
    mix_pools, rpb = gen_pools("bulk", WRITERS, ROWS, mix_tables)

    def sustained(w: int):
        from greptimedb_tpu.servers.http import _ingest_columns
        from greptimedb_tpu.servers.protocols import parse_arrow_bulk

        for body in mix_pools[w]:
            if stop.is_set():
                break
            _ingest_columns(db, mix_tables[w], parse_arrow_bulk(body))
            mixed_rows[0] += rpb

    writers = [threading.Thread(target=sustained, args=(w,))
               for w in range(WRITERS)]
    t_mix = time.perf_counter()
    for t in writers:
        t.start()
    mixed_ms = []
    while time.perf_counter() - t_mix < MIXED_S:
        t0 = time.perf_counter()
        db.sql(window_sql(q_table, q_lo))
        mixed_ms.append((time.perf_counter() - t0) * 1000)
    stop.set()
    for t in writers:
        t.join()
    mix_wall = time.perf_counter() - t_mix
    warm_mixed = float(np.median(mixed_ms))
    mixed_rate = mixed_rows[0] / mix_wall
    log(f"  warm median under sustained ingest {warm_mixed:.1f} ms "
        f"({len(mixed_ms)} queries; ingest {mixed_rate:,.0f} rows/s "
        f"alongside)")

    best, best_w = max((bulk_nw, WRITERS), (bulk_1w, 1))
    line = {
        "metric": "ingest_rows_per_s",
        "value": round(best, 1),
        "unit": "rows/s",
        "writers_best": best_w,
        "bulk_1w_rows_per_s": round(bulk_1w, 1),
        "bulk_multi_rows_per_s": round(bulk_nw, 1),
        "writers": WRITERS,
        "legacy_rows_per_s": round(legacy_bulk, 1),
        "speedup_vs_legacy": round(best / legacy_bulk, 2),
        "influx_rows_per_s": round(influx_nw, 1),
        "influx_legacy_rows_per_s": round(legacy_influx, 1),
        "object_decode_rows": int(vec_decode),
        "wal_flushes": wal_flushes,
        "wal_sync": WAL_SYNC,
        "warm_query_solo_ms": round(warm_solo, 2),
        "warm_query_mixed_ms": round(warm_mixed, 2),
        "mixed_ingest_rows_per_s": round(mixed_rate, 1),
        "backend": backend,
    }
    print(json.dumps(line), flush=True)
    db.close()
    tmp.cleanup()


if __name__ == "__main__":
    main()
