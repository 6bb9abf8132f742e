#!/usr/bin/env python
"""PromQL north-star benchmark: sum by(pod)(rate(val[5m])) at 1M series.

BASELINE.md target #2: "Beat PromQL `sum by(pod)(rate(http_requests_total
[5m]))` at 1M-10M series cardinality. Metric of record: PromQL range-query
rows/sec/chip."  The reference has no published absolute number for this
query (its TSBS suite doesn't include it), so the line of record reports
absolute throughput: samples scanned per second of evaluation, per chip.

Dataset: SERIES time series (pods x containers), SAMPLES samples each at
15 s cadence, ingested through the real write path (tag factorize ->
memtable -> flush).  The query runs through promql/engine.py — matcher
resolution, the counter-rate window kernel with Prometheus extrapolation
(reference src/promql/src/functions/extrapolate_rate.rs:56 semantics at
src/query/src/promql/planner.rs:383 scale), and the sum-by segment fold.

Prints ONE json line:
  {"metric": "promql_rate_sum_rows_per_s", "value": <samples/s>,
   "unit": "rows/s", ...}   (higher is better)

Env knobs: GREPTIME_PROMQL_SERIES (default 1,000,000),
GREPTIME_PROMQL_SAMPLES (per series, default 8),
GREPTIME_BENCH_DATA (cache dir), GREPTIME_BENCH_BUDGET_S (default 420).
"""

from __future__ import annotations

import json
import os
import signal
import sys
import time

import numpy as np

SERIES = int(os.environ.get("GREPTIME_PROMQL_SERIES", "1000000"))
SAMPLES = int(os.environ.get("GREPTIME_PROMQL_SAMPLES", "8"))
BUDGET_S = float(os.environ.get("GREPTIME_BENCH_BUDGET_S", "420"))
START = time.time()
STEP_MS = 15_000  # 15s scrape interval
T0 = 1700000000000
DATA_DIR = os.environ.get(
    "GREPTIME_BENCH_DATA",
    os.path.join(os.path.dirname(__file__), ".bench_data"),
)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


_times: list[float] = []
_warmup_times: list[float] = []  # SIGTERM fallback before any timed run
_emitted = False
_backend = "unknown"
_cache_stats: dict = {}  # PromLayoutCache counters (resident PromQL state)


def _line(times: list[float], warmup: bool = False) -> str:
    sec = float(np.median(times))
    total_samples = SERIES * SAMPLES
    line = {
        "metric": "promql_rate_sum_rows_per_s",
        "value": round(total_samples / sec, 1),
        "unit": "rows/s",
        "vs_baseline": None,  # no published reference number for this query
        "backend": _backend,
        "series": SERIES,
        "samples_per_series": SAMPLES,
        "eval_ms": round(sec * 1000, 1),
        "runs": len(times),
    }
    # cold/warm attribution (round-5 gap: the one recorded run could not
    # distinguish compile+build from steady state): cold = first eval
    # (JIT compile + resident layout build), warm = this line's median
    if _warmup_times:
        line["eval_ms_cold"] = round(_warmup_times[0] * 1000, 1)
    line["eval_ms_warm"] = round(sec * 1000, 1)
    if _cache_stats:
        line["promql_cache"] = _cache_stats
    notes = []
    if SERIES != 1_000_000:
        notes.append(f"reduced cardinality {SERIES}/1000000")
    if warmup:
        # killed before any warm run: the number includes JIT compile
        # and understates steady-state throughput
        notes.append("warmup-only (includes compile)")
    if notes:
        line["note"] = "; ".join(notes)
    return json.dumps(line)


def emit(times: list[float] | None = None) -> None:
    global _emitted
    times = times if times is not None else _times
    if _emitted or not times:
        return
    _emitted = True
    print(_line(times), flush=True)


def _on_term(signum, frame):
    # async-signal context: the main thread may hold the stdout lock, so
    # print() could raise a reentrancy error — raw os.write instead
    global _emitted
    if not _emitted:
        times = _times or _warmup_times[-1:]
        if times:
            _emitted = True
            # only the FIRST warmup run includes JIT compile; the second
            # is a clean post-compile measurement
            wu = not _times and len(_warmup_times) < 2
            os.write(1, (_line(times, warmup=wu) + "\n").encode())
    os._exit(0 if _emitted else 1)


def build_db():
    from greptimedb_tpu.standalone import GreptimeDB
    from greptimedb_tpu.storage.region import RegionOptions

    home = os.path.join(DATA_DIR, f"promql_{SERIES}_{SAMPLES}")
    marker = os.path.join(home, "ready")
    db = GreptimeDB(home, region_options=RegionOptions(
        wal_enabled=False, flush_threshold_bytes=1 << 40))
    db.sql(
        "CREATE TABLE IF NOT EXISTS http_requests_total (pod STRING, "
        "container STRING, ts TIMESTAMP(3) TIME INDEX, val DOUBLE, "
        "PRIMARY KEY (pod, container))"
    )
    if os.path.exists(marker):
        return db
    n_pods = max(SERIES // 10, 1)
    log(f"generating {SERIES:,} series x {SAMPLES} samples "
        f"({SERIES * SAMPLES:,} rows) ...")
    region = db._region_of("http_requests_total")
    pods = np.array([f"pod-{i}" for i in range(n_pods)], dtype=object)
    containers = np.array([f"c{i}" for i in range(10)], dtype=object)
    rng = np.random.default_rng(11)
    # counters increase ~10/s with jitter; ingest one timestep per write
    # (vectorized across all series, like a scrape)
    counters = rng.uniform(0, 1000, SERIES)
    pod_col = pods[np.arange(SERIES) // 10]
    cont_col = containers[np.arange(SERIES) % 10]
    t_wall = time.time()
    for k in range(SAMPLES):
        counters = counters + rng.uniform(100, 200, SERIES)
        region.write({
            "pod": pod_col,
            "container": cont_col,
            "ts": np.full(SERIES, T0 + k * STEP_MS, dtype=np.int64),
            "val": counters,
        })
        log(f"  scrape {k + 1}/{SAMPLES} ({time.time() - t_wall:.0f}s)")
    region.flush()
    with open(marker, "w") as f:
        f.write("ok")
    return db


def main() -> None:
    import jax

    signal.signal(signal.SIGTERM, _on_term)
    signal.signal(signal.SIGINT, _on_term)
    if os.environ.get("JAX_PLATFORMS"):
        jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])
    from greptimedb_tpu.compile.xla_cache import configure_xla_cache

    configure_xla_cache()

    global _backend
    db = build_db()
    _backend = jax.default_backend()
    log(f"jax devices: {jax.devices()} ({time.time() - START:.0f}s)")

    from greptimedb_tpu.promql.engine import PromEvaluator
    from greptimedb_tpu.promql.parser import parse_promql

    # instant query at the last scrape, 5m rate window covering all samples
    end_s = (T0 + (SAMPLES - 1) * STEP_MS) / 1000.0
    expr = parse_promql('sum by(pod) (rate(http_requests_total[5m]))')

    def run_once() -> float:
        t0 = time.time()
        ev = PromEvaluator(db, end_s, end_s, 1.0)
        res = ev.eval(expr)
        np.asarray(res.values)  # materialize
        dt = time.time() - t0
        assert res.num_series == max(SERIES // 10, 1), res.num_series
        # resident-cache counters (selection/sort/group hit-miss) for the
        # line of record, read from the telemetry registry (the numbers
        # /metrics serves) so the bench JSON and a scrape can never
        # disagree; per-eval events land in the stderr log
        try:
            from greptimedb_tpu.utils.telemetry import REGISTRY

            _cache_stats.clear()
            _cache_stats["bytes"] = int(REGISTRY.value(
                "greptime_cache_resident_bytes", ("promql",)))
            _cache_stats["entries"] = int(REGISTRY.value(
                "greptime_cache_entries", ("promql",)))
            ev_total = "greptime_cache_events_total"
            _cache_stats["rejects"] = int(REGISTRY.value(
                ev_total, ("promql", "any", "quota_reject")))
            _cache_stats["builds"] = sum(
                int(REGISTRY.value(ev_total, ("promql", kind, "build")))
                for kind in ("selection", "sort", "group"))
            _cache_stats["evictions"] = sum(
                int(REGISTRY.value(ev_total, ("promql", kind, "eviction")))
                for kind in ("selection", "sort", "group"))
            for kind in ("selection", "sort", "group"):
                for event in ("hit", "miss"):
                    _cache_stats[f"{kind}_{event}es" if event == "miss"
                                 else f"{kind}_{event}s"] = int(
                        REGISTRY.value(ev_total, ("promql", kind, event)))
            _cache_stats["last_eval_events"] = dict(ev.cache_events)
        except Exception as e:  # noqa: BLE001 — stats are best-effort
            log(f"promql cache stats unavailable: {e}")
        return dt

    log("warmup (compile) ...")
    first = run_once()
    _warmup_times.append(first)
    log(f"  first: {first * 1000:.0f} ms")
    second = run_once()
    _warmup_times.append(second)
    log(f"  second: {second * 1000:.0f} ms")

    # EMIT EARLY (round-4 verdict, weak item 1): the r04 driver capture
    # ended before this child printed anything — the line of record goes
    # out after 3 timed runs; any further runs only refine the stderr log
    deadline = START + BUDGET_S
    hard_cap = deadline + 300
    while len(_times) < 10:
        now = time.time()
        est = max(second, _times[-1] if _times else 0.0)
        if not (now + est < deadline or (est < 30 and now + est < hard_cap)):
            break
        _times.append(run_once())
        if len(_times) == 3:
            emit()
    if not _times:
        _times.append(second)
    log(f"runs: {[f'{t * 1000:.0f}' for t in _times]} ms "
        f"({time.time() - START:.0f}s elapsed)")
    emit()
    db.close()


if __name__ == "__main__":
    main()
