#!/usr/bin/env python
"""First proof that the served path runs on the chip: TSBS devops
cpu-only through the standalone server, over HTTP, in ONE process.

    python chip_smoke.py                  # the real run: needs one TPU
    python chip_smoke.py --mesh           # only the 4-device mesh path
    python chip_smoke.py --rehearse --scale 16 --hours 1   # CPU rehearsal

Deployment (source: BASELINE.md / upstream docs/benchmarks/tsbs/v0.12.0.md;
the benchmark of record is benchmark/run.py): ``--scale`` hosts (TSBS's name for the
host count, default 4000), one ``hostname`` tag, 10 ``usage_*`` DOUBLE
fields, one row per host every 10 s for ``--hours`` hours (default 12 —
the window double-groupby-all reads: 17.28M rows), values from
``--seed``.  History goes in through ``/v1/arrow/write``; the newest ten
minutes go in as InfluxDB line protocol.  WAL on, server defaults.

Each phase prints one JSON line as it ends.  Every answer is compared
with a plain numpy evaluation over the generated arrays, which knows
nothing of greptimedb_tpu.  Stored DOUBLE values compute in f32 on the
device (README, "TPU-first design decisions"), so aggregates are held to
rtol 1e-4 (f32 accumulation over up to 4320 values) and single values to
rtol 1e-6 (one f32 rounding); group keys, timestamps and counts are
exact.  Any failed check raises: no phase is allowed to fail quietly.

Last line of stdout, on a TPU only:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
``--rehearse`` lets the run proceed on whatever platform JAX has and
then says ``"ok": false`` with that platform's name: a rehearsal is
never a result.  The times printed are observations of one run, not
benchmark results.
"""

from __future__ import annotations

import argparse
import io
import json
import os
import sys
import tempfile
import time
import urllib.error
import urllib.parse
import urllib.request

import numpy as np

METRICS = [
    "usage_user", "usage_system", "usage_idle", "usage_nice", "usage_iowait",
    "usage_irq", "usage_softirq", "usage_steal", "usage_guest",
    "usage_guest_nice",
]
T0 = 1451606400000  # 2016-01-01, the TSBS epoch (ms)
STEP_MS = 10_000
LP_STEPS = 60  # the newest ten minutes arrive as line protocol
AGG_RTOL, AGG_ATOL = 1e-4, 1e-3
VAL_RTOL = 1e-6
PROM_FIELD = "usage_user"
PROM_RANGE_S, PROM_STEP_S = 300, 60


def emit(phase: str, t0: float, **counted) -> None:
    print(json.dumps({"phase": phase,
                      "seconds": round(time.time() - t0, 3), **counted}),
          flush=True)


# ---------------------------------------------------------------------------
# data (numpy only): a clipped random walk per (host, field), as TSBS
# ---------------------------------------------------------------------------

def generate(seed: int, scale: int, steps: int) -> np.ndarray:
    """[steps, scale, 10] float64 in [0, 100]."""
    rng = np.random.default_rng(seed)
    out = np.empty((steps, scale, len(METRICS)))
    state = rng.uniform(0, 100, size=(scale, len(METRICS)))
    chunk = 360
    for s in range(0, steps, chunk):
        n = min(chunk, steps - s)
        walk = rng.normal(0, 1, size=(n, scale, len(METRICS)))
        out[s:s + n] = np.clip(state[None] + np.cumsum(walk, axis=0), 0, 100)
        state = out[s + n - 1]
    return out


def hostnames(scale: int) -> np.ndarray:
    return np.array([f"host_{i}" for i in range(scale)], dtype=object)


# ---------------------------------------------------------------------------
# the wire
# ---------------------------------------------------------------------------

class Client:
    def __init__(self, port: int):
        self.base = f"http://127.0.0.1:{port}"

    def _open(self, path: str, data: bytes | None = None) -> bytes:
        req = urllib.request.Request(self.base + path, data=data)
        try:
            with urllib.request.urlopen(req, timeout=1100) as r:
                return r.read()
        except urllib.error.HTTPError as e:
            raise RuntimeError(f"{path.split('?')[0]} answered {e.code}: "
                               f"{e.read()[:2000]!r}") from None

    def sql(self, q: str) -> list[list]:
        body = json.loads(self._open(
            "/v1/sql", urllib.parse.urlencode({"sql": q}).encode()))
        if body.get("code") != 0:
            raise RuntimeError(f"sql failed: {body}")
        out = body["output"][0]
        return out["records"]["rows"] if "records" in out else []

    def arrow_write(self, table: str, body: bytes) -> int:
        return json.loads(self._open(
            f"/v1/arrow/write?table={table}", body))["rows"]

    def influx_write(self, body: bytes) -> None:
        self._open("/v1/influxdb/api/v2/write", body)

    def query_range(self, query: str, start_s: float, end_s: float,
                    step_s: int) -> list[dict]:
        body = json.loads(self._open(
            "/v1/prometheus/api/v1/query_range?" + urllib.parse.urlencode(
                {"query": query, "start": start_s, "end": end_s,
                 "step": step_s})))
        if body.get("status") != "success":
            raise RuntimeError(f"promql failed: {body}")
        return body["data"]["result"]


def load(cl: Client, data: np.ndarray) -> dict:
    import pyarrow as pa

    steps, scale, _ = data.shape
    cols = ", ".join(f"{m} DOUBLE" for m in METRICS)
    cl.sql("CREATE TABLE cpu (hostname STRING, ts TIMESTAMP(3) TIME INDEX, "
           f"{cols}, PRIMARY KEY (hostname))")
    hosts = pa.array(hostnames(scale))
    hist = max(steps - LP_STEPS, 0)
    # bodies stay well under the server's 64 MiB request limit
    per = max(1, (32 << 20) // (92 * scale))
    arrow_rows = arrow_bytes = 0
    for s in range(0, hist, per):
        n = min(per, hist - s)
        cols = {
            "hostname": pa.DictionaryArray.from_arrays(
                pa.array(np.tile(np.arange(scale, dtype=np.int32), n)),
                hosts),
            "ts": pa.array(np.repeat(
                T0 + np.arange(s, s + n, dtype=np.int64) * STEP_MS, scale)),
        }
        for j, m in enumerate(METRICS):
            cols[m] = pa.array(data[s:s + n, :, j].reshape(-1))
        table = pa.table(cols)
        sink = io.BytesIO()
        with pa.ipc.new_stream(sink, table.schema) as w:
            w.write_table(table)
        body = sink.getvalue()
        arrow_bytes += len(body)
        arrow_rows += cl.arrow_write("cpu", body)
    lp_rows = lp_bytes = 0
    for s in range(hist, steps, 10):
        lines = []
        for k in range(s, min(s + 10, steps)):
            ts_ns = (T0 + k * STEP_MS) * 1_000_000
            for h in range(scale):
                fields = ",".join(
                    f"{m}={v!r}" for m, v in zip(METRICS,
                                                 data[k, h].tolist()))
                lines.append(f"cpu,hostname=host_{h} {fields} {ts_ns}")
        body = "\n".join(lines).encode()
        lp_bytes += len(body)
        lp_rows += len(lines)
        cl.influx_write(body)
    if arrow_rows != hist * scale:
        raise RuntimeError(f"arrow acked {arrow_rows}, sent {hist * scale}")
    return {"rows": arrow_rows + lp_rows, "arrow_rows": arrow_rows,
            "arrow_bytes": arrow_bytes, "lp_rows": lp_rows,
            "lp_bytes": lp_bytes, "hosts": scale, "fields": len(METRICS),
            "values": (arrow_rows + lp_rows) * len(METRICS)}


# ---------------------------------------------------------------------------
# queries and their numpy references
# ---------------------------------------------------------------------------

def _win(k0: int, k1: int) -> str:
    return f"ts >= {T0 + k0 * STEP_MS} AND ts < {T0 + k1 * STEP_MS}"


def q_double_groupby_all(data, rng):
    """TSBS double-groupby-all: avg of all 10 fields by (hostname, hour)."""
    steps, scale, _ = data.shape
    hours = min(12, steps // 360)
    k0 = steps - hours * 360
    aggs = ", ".join(f"avg({m})" for m in METRICS)
    q = (f"SELECT hostname, date_trunc('hour', ts) AS hour, {aggs} FROM cpu "
         f"WHERE {_win(k0, steps)} GROUP BY hostname, hour")
    ref = data[k0:].reshape(hours, 360, scale, -1).mean(axis=1)
    names = hostnames(scale)

    def check(rows):
        assert len(rows) == scale * hours, (len(rows), scale * hours)
        got = {(r[0], r[1]): r[2:] for r in rows}
        want_keys = [(names[h], T0 + (k0 + b * 360) * STEP_MS)
                     for b in range(hours) for h in range(scale)]
        arr = np.array([got[k] for k in want_keys], dtype=float)
        np.testing.assert_allclose(
            arr, ref.reshape(-1, len(METRICS)), rtol=AGG_RTOL, atol=AGG_ATOL)
        return {"groups": len(rows)}

    return q, check


def q_single_groupby_1_1_1(data, rng):
    """TSBS single-groupby-1-1-1: max of 1 field, 1 host, per minute, 1 h."""
    steps, scale, _ = data.shape
    h = int(rng.integers(scale))
    k0 = int(rng.integers(steps // 360)) * 360
    q = (f"SELECT date_trunc('minute', ts) AS minute, max(usage_user) "
         f"FROM cpu WHERE hostname = 'host_{h}' AND {_win(k0, k0 + 360)} "
         "GROUP BY minute ORDER BY minute")
    ref = data[k0:k0 + 360, h, 0].reshape(60, 6).max(axis=1)

    def check(rows):
        assert [r[0] for r in rows] == [
            T0 + (k0 + 6 * i) * STEP_MS for i in range(60)]
        np.testing.assert_allclose([r[1] for r in rows], ref, rtol=VAL_RTOL)
        return {"groups": len(rows)}

    return q, check


def q_cpu_max_all_8(data, rng):
    """TSBS cpu-max-all-8: max of all 10 fields, 8 hosts, per hour, 8 h."""
    steps, scale, _ = data.shape
    hours = min(8, steps // 360)
    hs = sorted(rng.choice(scale, size=min(8, scale), replace=False).tolist())
    k0 = int(rng.integers(steps // 360 - hours + 1)) * 360
    aggs = ", ".join(f"max({m})" for m in METRICS)
    hosts = ", ".join(f"'host_{h}'" for h in hs)
    q = (f"SELECT date_trunc('hour', ts) AS hour, {aggs} FROM cpu "
         f"WHERE hostname IN ({hosts}) AND {_win(k0, k0 + hours * 360)} "
         "GROUP BY hour ORDER BY hour")
    ref = data[k0:k0 + hours * 360][:, hs].reshape(
        hours, 360 * len(hs), -1).max(axis=1)

    def check(rows):
        assert [r[0] for r in rows] == [
            T0 + (k0 + 360 * b) * STEP_MS for b in range(hours)]
        np.testing.assert_allclose(
            np.array([r[1:] for r in rows], dtype=float), ref, rtol=VAL_RTOL)
        return {"groups": len(rows)}

    return q, check


def q_stddev_by_host(data, rng):
    """An aggregate the resident-grid path declines (stddev is outside
    its operator set), so the row-path segment reduction runs."""
    steps, scale, _ = data.shape
    q = (f"SELECT hostname, stddev(usage_user) FROM cpu "
         f"WHERE {_win(0, steps)} GROUP BY hostname")
    ref = data[:, :, 0].std(axis=0, ddof=1)
    names = hostnames(scale)

    def check(rows):
        got = dict(rows)
        assert len(got) == scale, len(got)
        np.testing.assert_allclose(
            [got[n] for n in names], ref, rtol=AGG_RTOL, atol=AGG_ATOL)
        return {"groups": len(rows)}

    return q, check


def q_read_back(data, rng):
    """Rows acknowledged on the line-protocol route, read back."""
    steps, scale, _ = data.shape
    h = int(rng.integers(scale))
    k0 = max(steps - LP_STEPS, 0)
    cols = ", ".join(METRICS)
    q = (f"SELECT ts, {cols} FROM cpu WHERE hostname = 'host_{h}' "
         f"AND ts >= {T0 + k0 * STEP_MS} ORDER BY ts")

    def check(rows):
        assert [r[0] for r in rows] == [
            T0 + k * STEP_MS for k in range(k0, steps)]
        np.testing.assert_allclose(
            np.array([r[1:] for r in rows], dtype=float), data[k0:, h],
            rtol=VAL_RTOL)
        return {"rows": len(rows)}

    return q, check


def q_count_newest(data, rng):
    steps, scale, _ = data.shape
    k0 = max(steps - LP_STEPS, 0)
    q = f"SELECT count(*) FROM cpu WHERE ts >= {T0 + k0 * STEP_MS}"

    def check(rows):
        assert rows == [[(steps - k0) * scale]], rows
        return {"rows": rows[0][0]}

    return q, check


SQL_QUERIES = [
    ("double-groupby-all", q_double_groupby_all),
    ("single-groupby-1-1-1", q_single_groupby_1_1_1),
    ("cpu-max-all-8", q_cpu_max_all_8),
    ("stddev-by-host", q_stddev_by_host),
    ("read-back", q_read_back),
    ("count-newest", q_count_newest),
]
MESH_QUERIES = SQL_QUERIES[:1]


def prom_rate_reference(vals: np.ndarray, eval_ms: np.ndarray) -> np.ndarray:
    """Prometheus ``rate()`` (extrapolatedRate, counter semantics, window
    (t - range, t]) for samples every STEP_MS from T0.  vals [steps, S];
    returns [S, len(eval_ms)], NaN where a window holds < 2 samples."""
    steps, scale = vals.shape
    out = np.full((scale, len(eval_ms)), np.nan)
    rng_ms = PROM_RANGE_S * 1000
    for i, t in enumerate(eval_ms):
        lo = max((int(t) - rng_ms - T0) // STEP_MS + 1, 0)
        hi = min((int(t) - T0) // STEP_MS, steps - 1)
        n = hi - lo + 1
        if n < 2:
            continue
        w = vals[lo:hi + 1]
        d = np.diff(w, axis=0)
        delta = w[-1] - w[0] + np.where(d < 0, w[:-1], 0.0).sum(axis=0)
        first_t, last_t = T0 + lo * STEP_MS, T0 + hi * STEP_MS
        sampled = (last_t - first_t) / 1000.0
        avg = sampled / (n - 1)
        to_start = (first_t - (t - rng_ms)) / 1000.0
        to_end = (t - last_t) / 1000.0
        if to_start >= avg * 1.1:
            to_start = avg / 2
        if to_end >= avg * 1.1:
            to_end = avg / 2
        with np.errstate(divide="ignore", invalid="ignore"):
            to_zero = np.where(delta > 0, sampled * (w[0] / delta), np.inf)
        start = np.minimum(to_start, to_zero)
        out[:, i] = delta * (sampled + start + to_end) / sampled / PROM_RANGE_S
    return out


def run_promql(cl: Client, data: np.ndarray):
    steps, scale, _ = data.shape
    end_ms = T0 + steps * STEP_MS
    start_ms = end_ms - 3600_000
    eval_ms = np.arange(start_ms, end_ms + 1, PROM_STEP_S * 1000)
    query = (f'sum by (hostname)(rate(cpu{{__field__="{PROM_FIELD}"}}'
             f'[{PROM_RANGE_S}s]))')
    # counter-reset detection compares neighbouring samples, so the
    # reference reads them at the precision the device stores: f32
    vals = data[:, :, METRICS.index(PROM_FIELD)].astype(np.float32)
    ref = prom_rate_reference(vals.astype(np.float64), eval_ms)
    col = {int(t): i for i, t in enumerate(eval_ms)}

    def run():
        return cl.query_range(query, start_ms / 1000, end_ms / 1000,
                              PROM_STEP_S)

    def check(result):
        got = np.full_like(ref, np.nan)
        for series in result:
            h = int(series["metric"]["hostname"].split("_")[1])
            for t, v in series["values"]:
                got[h, col[int(round(float(t) * 1000))]] = float(v)
        np.testing.assert_allclose(got, ref, rtol=AGG_RTOL, atol=AGG_ATOL)
        return {"series": len(result), "steps": len(eval_ms)}

    return query, run, check


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def _compile_seconds() -> float:
    """What the program's own timer puts down to compilation: the
    ``xla_compile`` stage of the SQL kernels and of PromQL's window and
    fused programs.  The raw-row SELECT and the PromQL sort layout keep
    no such stage; their compile time shows only as first run minus warm
    run."""
    from greptimedb_tpu.utils.telemetry import REGISTRY

    return sum(child.sum for name, _k, _l, key, child in REGISTRY.snapshot()
               if (name, key) == ("greptime_query_stage_seconds",
                                  ("xla_compile",)))


def timed_runs(name: str, run, check, stats: dict) -> dict:
    """Three runs over HTTP; the first pays compilation and says so."""
    from greptimedb_tpu.query.physical import DISPATCH_STATS

    secs, counted = [], {}
    before = dict(DISPATCH_STATS)
    c0 = _compile_seconds()
    for i in range(3):
        t0 = time.time()
        out = run()
        secs.append(round(time.time() - t0, 4))
        if i == 0:
            compile_s = round(_compile_seconds() - c0, 3)
            moved = {k: DISPATCH_STATS[k] - before[k] for k in DISPATCH_STATS
                     if DISPATCH_STATS[k] != before[k]}
        counted = check(out)
    stats[name] = moved
    return {"query": name, "first_s_with_compile": secs[0],
            "compile_s": compile_s, "warm_s": secs[1:], "dispatch": moved,
            "correct": True, **counted}


def start_server(data_home: str):
    """The way ``greptimedb_tpu.cli`` ``cmd_standalone`` starts it, with
    the default options: WAL on, HTTP on a free port."""
    from greptimedb_tpu.servers import HttpServer
    from greptimedb_tpu.standalone import GreptimeDB
    from greptimedb_tpu.storage.region import RegionOptions
    from greptimedb_tpu.utils.config import StandaloneOptions

    opts = StandaloneOptions()
    db = GreptimeDB(
        data_home,
        region_options=RegionOptions(
            flush_threshold_bytes=opts.storage.flush_threshold_mb << 20,
            compaction_window_ms=(
                opts.storage.compaction_window_hours * 3600_000),
            compaction_trigger_files=opts.storage.compaction_trigger_files,
            wal_enabled=opts.wal.provider != "noop",
            wal_sync=opts.wal.sync,
        ),
        cache_capacity_bytes=opts.storage.cache_capacity_gb << 30,
    )
    srv = HttpServer(db, host="127.0.0.1", port=0)
    srv.start()
    return db, srv


def checked_counters() -> dict:
    """greptime_compile_cache_events_total; a fallback or a persist
    error means a program was rebuilt behind the scenes — that fails."""
    from greptimedb_tpu.utils.telemetry import REGISTRY

    c = {ev: int(REGISTRY.value("greptime_compile_cache_events_total",
                                (ev,)))
         for ev in ("fallback", "persist_error", "aot_hit", "build",
                    "persist")}
    if c["fallback"] or c["persist_error"]:
        raise RuntimeError(f"compile-cache fallback counters moved: {c}")
    return c


def phase_query(cl, data, seed, queries, stats) -> None:
    rng = np.random.default_rng(seed)  # which hosts and hours are asked
    for name, make in queries:
        t0 = time.time()
        q, check = make(data, rng)
        emit("query", t0, **timed_runs(
            name, lambda q=q: cl.sql(q), check, stats))
    t0 = time.time()
    query, run, check = run_promql(cl, data)
    emit("query", t0, **timed_runs("promql-rate-sum", run, check, stats),
         promql=query)


def phase_check(db, devices, stats: dict, mesh: bool) -> None:
    t0 = time.time()
    if not stats["double-groupby-all"].get("grid_bm"):
        raise RuntimeError(f"double-groupby-all missed the bucket-major "
                           f"grid path: {stats}")
    row_form = None
    if not mesh:
        for name in ("single-groupby-1-1-1", "cpu-max-all-8"):
            if not stats[name].get("grid"):
                raise RuntimeError(f"{name} missed the grid path: {stats}")
        forms = [k for k in ("sorted", "scatter")
                 if stats["stddev-by-host"].get(k)]
        if len(forms) != 1:
            raise RuntimeError(f"row-path segment form unclear: {stats}")
        row_form = forms[0]
    grid, _bounds = db.grid_table("cpu", None)
    if grid is None:
        raise RuntimeError("cpu has no resident grid")
    on = sorted(d.id for d in grid.values.sharding.device_set)
    if on != sorted(d.id for d in devices):
        raise RuntimeError(f"resident grid on devices {on}, not {devices}")
    shard_bytes = [int(s.data.nbytes) for s in grid.values.addressable_shards]
    if mesh:
        total = grid.values.nbytes
        if len(shard_bytes) != len(devices) or any(
                abs(b - total / len(devices)) > 0.05 * total
                for b in shard_bytes):
            raise RuntimeError(
                f"grid not spread evenly: {shard_bytes} of {total}")
    emit("check", t0, grid_shape=list(grid.values.shape),
         grid_bytes=int(grid.nbytes()), grid_devices=on,
         grid_platform=devices[0].platform, shard_bytes=shard_bytes,
         row_path_segment_form=row_form,
         compile_cache_events=checked_counters())


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--scale", type=int, default=4000,
                    help="TSBS scale: the number of hosts")
    ap.add_argument("--hours", type=int, default=12)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on whatever platform JAX has; never ok")
    ap.add_argument("--mesh", action="store_true",
                    help="only the mesh path; needs four devices")
    args = ap.parse_args()
    if args.hours < 1 or args.scale < 1:
        ap.error("--hours and --scale must be at least 1")
    want_devices = 4 if args.mesh else 1

    # ---- device ------------------------------------------------------
    t0 = time.time()
    import jax

    from greptimedb_tpu import native
    from greptimedb_tpu.compile.xla_cache import (configure_xla_cache,
                                                  xla_cache_stats)

    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if dev["platform"] != "tpu" and not args.rehearse:
        raise SystemExit(f"chip_smoke: device phase: JAX found {dev}, "
                         "not a TPU (--rehearse runs without one)")
    if len(devices) != want_devices:
        raise SystemExit(f"chip_smoke: device phase: {len(devices)} devices "
                         f"visible, this run needs {want_devices}")
    cache_dir = configure_xla_cache()
    native_built = native.build()
    emit("device", t0, **dev, xla_cache_dir=cache_dir,
         native_built=native_built, native_loaded=native.lib() is not None)

    steps = args.hours * 360
    stats: dict = {}
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as home:
        db, srv = start_server(home)
        try:
            if (db.mesh is not None) != args.mesh:
                raise RuntimeError(f"mesh={db.mesh} with --mesh={args.mesh}")
            cl = Client(srv.port)
            # ---- load ------------------------------------------------
            t0 = time.time()
            data = generate(args.seed, args.scale, steps)
            gen_s = round(time.time() - t0, 3)
            t0 = time.time()
            emit("load", t0, generate_seconds=gen_s, hours=args.hours,
                 **load(cl, data))
            # ---- query, check ----------------------------------------
            phase_query(cl, data, args.seed,
                        MESH_QUERIES if args.mesh else SQL_QUERIES, stats)
            phase_check(db, devices, stats, args.mesh)
        finally:
            srv.stop()
            db.close(flush=True)
        if not args.mesh:
            # ---- reopen: same data home, same process ----------------
            t0 = time.time()
            db, srv = start_server(home)
            try:
                open_s = round(time.time() - t0, 3)
                name, make = SQL_QUERIES[0]
                q, check = make(data, None)
                cl = Client(srv.port)
                res = timed_runs(name, lambda: cl.sql(q), check, stats)
                emit("reopen", t0, open_seconds=open_s,
                     aot_hits=db.plan_compiler.aot_hits,
                     compile_cache_events=checked_counters(), **res)
            finally:
                srv.stop()
                db.close(flush=True)
    print(json.dumps({"phase": "xla_cache", **xla_cache_stats()}),
          flush=True)
    ok = dev["platform"] == "tpu"
    print(json.dumps({"ok": ok, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
