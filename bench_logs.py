#!/usr/bin/env python
"""Loki log-search benchmark: fingerprint prefilter A/B vs the host path.

Pushes N mostly-unique log lines (64 streams) through the real
`/v1/loki/api/v1/push` surface, then drives warm LogQL `query_range`
queries — substring (`|=`), regex (`|~`) and `count_over_time` — twice:

  A) GREPTIME_FULLTEXT=on  — fingerprint matrix resident on device,
     `(row_fp & qmask) == qmask` prefilter + exact verification of
     candidates, verified-vocabulary memo across repeats;
  B) GREPTIME_FULLTEXT=off — the host path twin: the same predicate
     walks every distinct line on every evaluation.

Results are asserted bit-identical between the two runs before any
timing is reported.  Counters come from the telemetry registry (the
numbers /metrics serves): candidates, verified, matched (the
false-positive ratio), and resident fingerprint bytes.

Prints ONE json line (tee to BENCH_r12.json):
  {"metric": "loki_warm_line_filter_speedup", "value": <median A/B
   speedup over the |= queries>, "queries": {...}, ...}

Env knobs: GREPTIME_BENCH_LOG_LINES (default 1_000_000),
GREPTIME_BENCH_LOG_REPS (warm repetitions, default 5),
GREPTIME_BENCH_LOG_BATCH (lines per push, default 20_000).
"""

from __future__ import annotations

import json
import os
import random
import sys
import time
import urllib.parse
import urllib.request

N_LINES = int(os.environ.get("GREPTIME_BENCH_LOG_LINES", "1000000"))
REPS = int(os.environ.get("GREPTIME_BENCH_LOG_REPS", "5"))
BATCH = int(os.environ.get("GREPTIME_BENCH_LOG_BATCH", "20000"))
T0_NS = 1_700_000_000_000_000_000
SPAN_S = 3600  # one hour of logs

APPS = [f"svc-{i}" for i in range(16)]
LEVELS = ["info", "warn", "error", "debug"]
PATHS = ["/api/v1/items", "/api/v1/users", "/healthz", "/checkout",
         "/search", "/login"]
ERRORS = ["context deadline exceeded", "connection refused",
          "connection reset by peer", "upstream timeout",
          "tls handshake failure", "queue overflow"]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def gen_lines(rng: random.Random, n: int):
    """(app, level, ts_ns, line) — realistic mostly-unique lines."""
    out = []
    for i in range(n):
        app = rng.choice(APPS)
        level = rng.choice(LEVELS)
        ts = T0_NS + int(i * (SPAN_S * 1e9) / n)
        rid = rng.randrange(10**12)
        path = rng.choice(PATHS)
        if level == "error" and rng.random() < 0.6:
            line = (f"request failed method=GET path={path} "
                    f"req_id={rid:x} err={rng.choice(ERRORS)!r}")
        else:
            line = (f"handled method=GET path={path} status="
                    f"{rng.choice([200, 201, 204, 301, 404])} "
                    f"req_id={rid:x} dur={rng.random()*2:.3f}s")
        out.append((app, level, ts, line))
    return out


def push_all(base: str, rows) -> float:
    t0 = time.time()
    for lo in range(0, len(rows), BATCH):
        chunk = rows[lo:lo + BATCH]
        streams: dict = {}
        for app, level, ts, line in chunk:
            streams.setdefault((app, level), []).append([str(ts), line])
        payload = {"streams": [
            {"stream": {"app": a, "level": lv}, "values": vals}
            for (a, lv), vals in streams.items()]}
        req = urllib.request.Request(
            base + "/v1/loki/api/v1/push",
            data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json",
                     "X-Scope-OrgID": "bench"})
        with urllib.request.urlopen(req, timeout=300) as r:
            assert r.status == 204, r.status
        if (lo // BATCH) % 10 == 0:
            log(f"  pushed {lo + len(chunk):,}/{len(rows):,}")
    return time.time() - t0


def run_query(base: str, query: str) -> tuple[float, dict]:
    qs = urllib.parse.urlencode({
        "query": query,
        "start": str(T0_NS // 10**9),
        "end": str(T0_NS // 10**9 + SPAN_S),
        "step": str(SPAN_S // 30),
        "limit": "200",
    })
    t0 = time.perf_counter()
    with urllib.request.urlopen(
            base + "/v1/loki/api/v1/query_range?" + qs,
            timeout=600) as r:
        body = json.loads(r.read())
    ms = (time.perf_counter() - t0) * 1000
    assert body["status"] == "success", body
    return ms, body["data"]


def counters() -> dict:
    from greptimedb_tpu.utils.telemetry import REGISTRY

    cand = REGISTRY.value("greptime_fulltext_candidates_total")
    ver = REGISTRY.value("greptime_fulltext_verified_total")
    mat = REGISTRY.value("greptime_fulltext_matched_total")
    return {
        "candidates": int(cand),
        "verified": int(ver),
        "matched": int(mat),
        "false_positive_ratio": round((ver - mat) / ver, 4) if ver else 0.0,
        "scanned_excluded": int(
            REGISTRY.value("greptime_fulltext_scanned_total")),
        "queries_prefilter": int(REGISTRY.value(
            "greptime_fulltext_queries_total", ("prefilter",))),
        "queries_memo": int(REGISTRY.value(
            "greptime_fulltext_queries_total", ("memo",))),
        "resident_bytes": int(
            REGISTRY.value("greptime_fulltext_resident_bytes")),
    }


def main() -> None:
    import jax

    from greptimedb_tpu.compile.xla_cache import configure_xla_cache

    configure_xla_cache()
    from greptimedb_tpu.servers import HttpServer
    from greptimedb_tpu.standalone import GreptimeDB

    os.environ["GREPTIME_FULLTEXT"] = "on"
    rng = random.Random(12)
    log(f"generating {N_LINES:,} lines ...")
    rows = gen_lines(rng, N_LINES)
    db = GreptimeDB()
    srv = HttpServer(db, port=0)
    srv.start()
    base = f"http://127.0.0.1:{srv.port}"
    t_push = push_all(base, rows)
    log(f"pushed {N_LINES:,} lines in {t_push:.1f}s "
        f"({N_LINES / t_push:,.0f} lines/s)")

    queries = {
        "substr_common": '{app=~".+"} |= "context deadline"',
        "substr_rare": '{app=~".+"} |= "tls handshake failure"',
        "regex": '{app=~".+"} |~ "deadline exceeded|connection refused"',
        "count_over_time":
            'sum by (app) (count_over_time({level="error"} '
            '|= "request failed" [2m]))',
    }

    def timed_pass(tag: str) -> tuple[dict, dict]:
        medians, payloads = {}, {}
        for name, q in queries.items():
            cold_ms, _ = run_query(base, q)  # build/refresh state
            times = []
            for _ in range(REPS):
                ms, data = run_query(base, q)
                times.append(ms)
            times.sort()
            medians[name] = times[len(times) // 2]
            payloads[name] = data
            log(f"  [{tag}] {name}: cold {cold_ms:.0f} ms, "
                f"warm median {medians[name]:.0f} ms")
        return medians, payloads

    log("pass A: GREPTIME_FULLTEXT=on")
    a_ms, a_payloads = timed_pass("on")
    ctrs = counters()
    log("pass B: GREPTIME_FULLTEXT=off (host path twin)")
    os.environ["GREPTIME_FULLTEXT"] = "off"
    b_ms, b_payloads = timed_pass("off")
    os.environ["GREPTIME_FULLTEXT"] = "on"

    parity_ok = all(a_payloads[k] == b_payloads[k] for k in queries)
    speedups = {k: round(b_ms[k] / a_ms[k], 2) for k in queries}
    substr = sorted(speedups[k] for k in ("substr_common", "substr_rare"))
    line = {
        "metric": "loki_warm_line_filter_speedup",
        "value": substr[len(substr) // 2],
        "n_lines": N_LINES,
        "push_lines_per_s": round(N_LINES / t_push),
        "warm_ms_fulltext": {k: round(v, 1) for k, v in a_ms.items()},
        "warm_ms_host": {k: round(v, 1) for k, v in b_ms.items()},
        "speedup": speedups,
        "parity_ok": parity_ok,
        "fulltext": ctrs,
        "reps": REPS,
        "backend": jax.default_backend(),
    }
    print(json.dumps(line))
    srv.stop()
    db.close()
    if not parity_ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
