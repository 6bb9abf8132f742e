#!/usr/bin/env python
"""Mixed-workload SLO soak: the closed-loop observatory acceptance gate.

Runs sustained bulk ingest, a dashboard query storm, log search and a
control-plane streaming flow simultaneously — with the integrity
scrubber, AOT warmup and journal drains underneath on the budgeted idle
economy, and one live flow failover mid-soak — then induces a latency
storm (objective override) and verifies the observatory's closed loop:

  - zero SLO-accounting gaps: every scheduler-submitted query that got
    past admission lands in EXACTLY one (tenant, class, protocol)
    sketch (``slo.total_recorded()`` vs the bench's own count);
  - burn-rate alerts FIRE during the induced storm (fast 1h/5m pair),
    background admission is closed while they fire, and the alerts
    CLEAR once the storm passes;
  - background idle consumers show nonzero grants with no consumer
    starved;
  - warm dashboard medians are unchanged with ``GREPTIME_SLO=off``
    (A/B: a second instance on the same data with the observatory
    never imported).

Gates on p99/SLO assertions, not solo medians.  Prints ONE json line
and writes it to ``BENCH_r18.json`` (override the path with
``GREPTIME_BENCH_OUT``; empty disables the file).

Env knobs: GREPTIME_BENCH_SOAK_S (mixed phase, default 6),
GREPTIME_BENCH_STORM_S (default 3), GREPTIME_BENCH_SCALE (hosts,
default 12), GREPTIME_BENCH_CLIENTS (dashboard clients, default 2).
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import threading
import time

# observatory knobs land BEFORE any greptimedb_tpu import (setdefault:
# the slow-tier test and operators can override)
os.environ.setdefault("GREPTIME_SLO_SLOT_S", "0.5")  # 5m window = 2.5 s
os.environ.setdefault("GREPTIME_SLO_MIN_SAMPLES", "25")
os.environ.setdefault("GREPTIME_SLO_THRESHOLD_MS", "500")
os.environ.setdefault("GREPTIME_SCRUB", "on")
os.environ.setdefault("GREPTIME_SCRUB_INTERVAL_S", "0")

SOAK_S = float(os.environ.get("GREPTIME_BENCH_SOAK_S", "6"))
STORM_S = float(os.environ.get("GREPTIME_BENCH_STORM_S", "3"))
SCALE = int(os.environ.get("GREPTIME_BENCH_SCALE", "12"))
CLIENTS = int(os.environ.get("GREPTIME_BENCH_CLIENTS", "2"))
T0 = 1451606400000
STEP_MS = 10_000
MINUTES = 20


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def build_db(home: str):
    import numpy as np

    from greptimedb_tpu.standalone import GreptimeDB

    db = GreptimeDB(home)
    db.sql("CREATE TABLE cpu (hostname STRING, ts TIMESTAMP(3) TIME "
           "INDEX, v0 DOUBLE, v1 DOUBLE, v2 DOUBLE, "
           "PRIMARY KEY (hostname))")
    db.sql("CREATE TABLE logs (app STRING, ts TIMESTAMP(3) TIME INDEX, "
           "line STRING, PRIMARY KEY (app))")
    rng = np.random.default_rng(7)
    samples = MINUTES * 60_000 // STEP_MS
    rows = []
    for h in range(SCALE):
        for i in range(samples):
            v = rng.uniform(0, 100, 3)
            rows.append(f"('host_{h}', {T0 + i * STEP_MS}, "
                        f"{v[0]:.2f}, {v[1]:.2f}, {v[2]:.2f})")
    for c in range(0, len(rows), 500):
        db.sql("INSERT INTO cpu VALUES " + ",".join(rows[c:c + 500]))
    lrows = []
    words = ["GET", "POST", "timeout", "error", "refused", "ok"]
    for i in range(2000):
        w = words[i % len(words)]
        lrows.append(f"('svc-{i % 8}', {T0 + i * 500}, "
                     f"'req {i} {w} /api/v{i % 3}')")
    for c in range(0, len(lrows), 500):
        db.sql("INSERT INTO logs VALUES " + ",".join(lrows[c:c + 500]))
    # flush so the scrubber has SSTs to verify on idle capacity
    db.sql("ADMIN flush_table('cpu')")
    db.sql("ADMIN flush_table('logs')")
    return db


def dash_sql(i: int) -> str:
    lo = T0 + (i % MINUTES) * 60_000
    return (f"SELECT hostname, avg(v0), max(v1) FROM cpu "
            f"WHERE ts >= {lo} AND ts < {lo + 300_000} GROUP BY hostname")


LOG_SQL = "SELECT count(line) FROM logs WHERE line LIKE '%timeout%'"


class Counted:
    """Thread-safe submit wrapper enforcing the accounting rule: a
    submit that got PAST admission (returned, or raised anything but
    ResourcesExhausted) must land in exactly one sketch."""

    def __init__(self, sched):
        from greptimedb_tpu.errors import ResourcesExhausted

        self.sched = sched
        self._RE = ResourcesExhausted
        self._lock = threading.Lock()
        self.recorded_expected = 0
        self.rejected = 0
        self.errors = 0

    def submit(self, sql: str, **kw):
        held = kw.pop("held", False)
        hold: list = [] if held else None
        try:
            r = self.sched.submit(sql, slo_hold=hold, **kw)
        except self._RE:
            with self._lock:
                self.rejected += 1
            return None
        except Exception:  # noqa: BLE001 — errored entries still record
            with self._lock:
                self.recorded_expected += 1
                self.errors += 1
            return None
        if held:
            # the http serialization twin: the sample covers the full
            # submit -> bytes-ready span
            self.sched.record_held(hold)
        with self._lock:
            self.recorded_expected += 1
        return r


def run_phase(counted, duration_s: float, protocols=("http",)):
    """CLIENTS dashboard clients + 1 log-search client + 1 ingest
    client, closed-loop for duration_s; returns latencies (ms)."""
    stop_at = time.perf_counter() + duration_s
    lat: list[list[float]] = [[] for _ in range(CLIENTS)]

    def dash(ci: int):
        i = ci
        while time.perf_counter() < stop_at:
            t0 = time.perf_counter()
            counted.submit(dash_sql(i), protocol="http", held=(i % 2 == 0))
            lat[ci].append((time.perf_counter() - t0) * 1000)
            i += 1

    def logsearch():
        while time.perf_counter() < stop_at:
            counted.submit(LOG_SQL, protocol="sql")
            time.sleep(0.01)

    def ingest():
        i = 0
        while time.perf_counter() < stop_at:
            ts = T0 + (MINUTES * 60_000) + i * 1000
            counted.submit(
                f"INSERT INTO cpu VALUES ('host_0', {ts}, 1.0, 2.0, 3.0)",
                protocol="http")
            i += 1
            time.sleep(0.005)

    threads = [threading.Thread(target=dash, args=(ci,))
               for ci in range(CLIENTS)]
    threads.append(threading.Thread(target=logsearch))
    threads.append(threading.Thread(target=ingest))
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return [v for lane in lat for v in lane]


def pct(xs, p):
    import numpy as np

    return float(np.percentile(np.asarray(xs), p)) if xs else None


def ab_warm_medians(sched_on, sched_off, rounds: int = 6,
                    per: int = 25) -> tuple[float, float]:
    """Interleaved A/B warm medians: alternating batches on the two
    instances so machine-wide drift (GC, other tenants of the box)
    lands on both sides instead of biasing whichever ran second.
    Measured on the logs table — the soak's ingest thread grows cpu on
    the ON instance only, which would skew a cpu-table comparison."""
    import numpy as np

    for s in (sched_on, sched_off):
        for _ in range(10):
            s.submit(LOG_SQL)
    on: list[float] = []
    off: list[float] = []
    for _ in range(rounds):
        for s, xs in ((sched_on, on), (sched_off, off)):
            for _ in range(per):
                t0 = time.perf_counter()
                s.submit(LOG_SQL)
                xs.append((time.perf_counter() - t0) * 1000)
    return (float(np.median(np.asarray(on))),
            float(np.median(np.asarray(off))))


def main() -> None:
    import jax

    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    from greptimedb_tpu.compile.xla_cache import configure_xla_cache

    configure_xla_cache()
    from greptimedb_tpu.flow.cluster import FlowControlPlane, Flownode
    from greptimedb_tpu.query.parser import parse_sql
    from greptimedb_tpu.utils.telemetry import REGISTRY

    base = tempfile.mkdtemp(prefix="soak_")
    t_build = time.time()
    db = build_db(os.path.join(base, "on"))
    log(f"built soak db ({time.time() - t_build:.0f}s)")
    sched, slo, eco = db.scheduler, db.slo, db.idle_economy
    assert sched is not None and slo is not None and eco is not None, (
        "bench_soak needs the scheduler + SLO observatory armed")

    # control-plane streaming flow over the live cpu table, 2 flownodes
    plane = FlowControlPlane(db.kv)
    nodes = [Flownode(i, db) for i in range(2)]
    t0ms = time.time() * 1000.0
    for n in nodes:
        plane.register_flownode(n)
        n.heartbeat(t0ms)
    plane.create_flow(parse_sql(
        "CREATE FLOW soak_flow SINK TO cpu_agg AS "
        "SELECT count(v0) FROM cpu")[0])
    owner = plane.nodes[plane.route("soak_flow")]
    survivor = next(n for n in plane.nodes.values() if n is not owner)
    plane.run_all()
    owner.engine.checkpoint_now()

    counted = Counted(sched)
    base_recorded = slo.total_recorded()

    # ---- phase 1: mixed workload, one live failover mid-phase --------
    log(f"phase mixed: {CLIENTS}+2 clients x {SOAK_S}s ...")
    half = SOAK_S / 2
    lat1 = run_phase(counted, half)
    owner.alive = False
    survivor.heartbeat(time.time() * 1000.0)
    moved = plane.tick()
    failover_ok = moved == ["soak_flow"] and \
        survivor.engine.ckpt_epoch is not None
    log(f"  failover moved={moved} epoch={survivor.engine.ckpt_epoch}")
    plane.run_all()
    lat1 += run_phase(counted, half)
    p99_mixed = pct(lat1, 99)

    # ---- phase 2: induced latency storm ------------------------------
    # the alert is polled WHILE the storm runs (the honest semantics —
    # and robust to low storm throughput under contention: one
    # post-storm sample can catch a short window below min_samples)
    log(f"phase storm: objective override x {STORM_S}s ...")
    slo.set_objective("default", 0.01)  # everything breaches
    storm = threading.Thread(target=run_phase,
                             args=(counted, STORM_S + 2.0))
    storm.start()
    alerts: list = []
    alert_fired = False
    poll_until = time.perf_counter() + STORM_S + 1.5
    while time.perf_counter() < poll_until:
        time.sleep(0.25)
        alerts = slo.alerts()
        if any(a["severity"] == "fast" for a in alerts):
            alert_fired = True
            break
    if os.environ.get("GREPTIME_BENCH_DEBUG"):
        from greptimedb_tpu.serving.slo import _WINDOWS
        sid = int(slo.clock() / slo.slot_s)
        for k, st in slo._keys.items():
            wins = {w: st.window(sid, n) for w, n in _WINDOWS.items()}
            log(f"  DEBUG {k}: sid={sid} wins={wins} "
                f"min_samples={slo.min_samples}")
    log(f"  alerts firing: {alerts}")
    # background admission must be CLOSED while the fast pair fires
    # (checked mid-storm, while the alert is live)
    rej0 = REGISTRY.value("greptime_scheduler_rejected_total",
                          ("default", "slo_budget")) or 0
    counted.submit("SELECT count(v0) FROM cpu", priority="background")
    rej1 = REGISTRY.value("greptime_scheduler_rejected_total",
                          ("default", "slo_budget")) or 0
    background_rejected = alert_fired and rej1 > rej0
    storm.join()

    # ---- phase 3: recovery — the alert must CLEAR --------------------
    log("phase recover: clean traffic until the short window forgets")
    slo.set_objective("default", 500.0)
    run_phase(counted, 4.0)  # > 5m window (2.5 s) + 1 s alert cache
    time.sleep(1.1)
    alert_cleared = not slo.fast_burn_active()

    # ---- gates --------------------------------------------------------
    recorded = slo.total_recorded() - base_recorded
    accounting_exact = recorded == counted.recorded_expected
    log(f"accounting: recorded={recorded} "
        f"expected={counted.recorded_expected} "
        f"(rejected={counted.rejected} errors={counted.errors})")
    consumers = eco.consumers()
    no_starvation = all(c["starved"] == 0 for c in consumers)
    idle_grants = sum(c["granted"] for c in consumers)
    log(f"idle economy: {consumers}")
    sink_rows = db.sql("SELECT count(*) FROM cpu_agg").rows[0][0]

    # ---- A/B: GREPTIME_SLO=off warm medians --------------------------
    os.environ["GREPTIME_SLO"] = "off"
    try:
        db_off = build_db(os.path.join(base, "off"))
        assert db_off.slo is None and db_off.idle_economy is None
        med_on, med_off = ab_warm_medians(sched, db_off.scheduler)
        db_off.close()
    finally:
        os.environ.pop("GREPTIME_SLO", None)
    ab_ratio = med_on / med_off if med_off else None
    ab_warm_ok = ab_ratio is not None and ab_ratio < 1.5
    log(f"A/B warm median: on={med_on:.2f} ms off={med_off:.2f} ms "
        f"(ratio {ab_ratio:.3f})")

    gates = {
        "accounting_exact": bool(accounting_exact),
        "alert_fired": bool(alert_fired),
        "alert_cleared": bool(alert_cleared),
        "background_rejected": bool(background_rejected),
        "idle_grants_nonzero": bool(idle_grants > 0),
        "no_starvation": bool(no_starvation),
        "failover_moved": bool(failover_ok),
        "flow_sink_live": bool(sink_rows and sink_rows > 0),
        "ab_warm_ok": bool(ab_warm_ok),
    }
    line = {
        "metric": "slo_soak_p99_ms",
        "value": round(p99_mixed, 2) if p99_mixed else None,
        "unit": "ms",
        "gates": gates,
        "recorded": recorded,
        "submitted_recorded": counted.recorded_expected,
        "admission_rejected": counted.rejected,
        "errors": counted.errors,
        "p50_mixed_ms": round(pct(lat1, 50), 2),
        "idle_consumers": {c["name"]: {
            "granted": c["granted"], "elapsed_ms": c["elapsed_ms"],
            "starved": c["starved"]} for c in consumers},
        "idle_throttled": eco.throttled,
        "warm_median_on_ms": round(med_on, 2),
        "warm_median_off_ms": round(med_off, 2),
        "ab_ratio": round(ab_ratio, 3) if ab_ratio else None,
        "status_rows": len(slo.status_rows()),
        "backend": jax.default_backend(),
        "scale": SCALE,
        "soak_s": SOAK_S,
    }
    print(json.dumps(line))
    out = os.environ.get(
        "GREPTIME_BENCH_OUT",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "BENCH_r18.json"))
    if out:
        with open(out, "w") as f:
            json.dump(line, f, indent=1)
            f.write("\n")
    db.close()
    failed = [k for k, v in gates.items() if not v]
    if failed:
        log(f"GATE FAILURES: {failed}")
        raise SystemExit(1)
    log("all gates passed")


if __name__ == "__main__":
    main()
