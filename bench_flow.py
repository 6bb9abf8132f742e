"""Flow-runtime bench: device one-dispatch folds vs the host
dict-of-partials engine (ISSUE 14 acceptance: >=10x warm fold throughput
at >=100k groups).

A/B over GREPTIME_FLOW_DEVICE: the same seeded, time-forward ingest
stream (appendable chunks -> the incremental pump path on the device
side, the data-driven chunk fold on the host side) drives one streaming
flow with the full decomposable aggregate surface.  Only the FOLD is
timed (flow_engine.on_write + run_all); region writes are outside the
window.  Tick latency comes from the greptime_flow_tick_duration_seconds
registry histogram; device dispatch counts from the runtime mirrors.

    python bench_flow.py [--groups 100000] [--rows 200000]
                         [--batches 4] [--host-batches 2] [--out BENCH_r14.json]

A small-scale exact parity pass (device sink == host sink) runs first so
the headline numbers are only reported for a configuration whose results
are known bit-exact.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

FLOW_SQL = ("CREATE FLOW bf SINK TO agg AS SELECT "
            "date_bin(INTERVAL '1 minute', ts) AS w, h, sum(v) AS s, "
            "count(*) AS c, avg(v) AS a, min(v) AS mn, max(v) AS mx "
            "FROM src GROUP BY w, h")


def _mk_db(device: bool):
    os.environ["GREPTIME_FLOW_DEVICE"] = "on" if device else "off"
    from greptimedb_tpu.standalone import GreptimeDB

    db = GreptimeDB()
    db.sql("CREATE TABLE src (h STRING, ts TIMESTAMP(3) TIME INDEX, "
           "v DOUBLE, PRIMARY KEY (h))")
    db.sql(FLOW_SQL)
    return db


def _batches(groups: int, rows: int, nbatches: int, seed: int = 7):
    """Seeded time-forward batches over a fixed group vocabulary: column
    arrays built once per batch (the bench driver itself stays
    vectorized — h is a fancy-indexed slice of a prebuilt vocab)."""
    rng = np.random.default_rng(seed)
    vocab = np.array([f"h{i}" for i in range(groups)], dtype=object)
    perm = rng.permutation(groups)
    out = []
    t = 0
    for b in range(nbatches):
        # rotated group sweep at ~6 rows/ms: every group keeps reporting
        # (the steady state of a live fleet), (series, ts) keys stay
        # unique by construction (a group repeats only >= groups/6 ms
        # later), and timestamps advance strictly so every batch is
        # APPENDABLE — the incremental one-dispatch pump path
        idx = (np.arange(rows, dtype=np.int64) + b * 7919) % groups
        hidx = perm[idx]
        ts = t + 1 + np.arange(rows, dtype=np.int64) // 6
        t = int(ts[-1])
        v = rng.integers(1, 100, size=rows).astype(np.float64)
        out.append({"h": vocab[hidx], "ts": ts, "v": v})
    return out


def _tick_stats(mode: str):
    from greptimedb_tpu.utils.telemetry import REGISTRY

    total = cnt = 0.0
    for m_name in ("greptime_flow_tick_duration_seconds",):
        metric = REGISTRY._metrics.get(m_name)
        if metric is None:
            continue
        for labels, child in metric._children.items():
            if labels and labels[-1] == mode:
                total += child.sum
                cnt += sum(child.counts)
    return (total / cnt * 1000.0) if cnt else None


def _run_side(device: bool, groups: int, rows: int, nbatches: int):
    db = _mk_db(device)
    region = db._region_of("src")
    batches = _batches(groups, rows, nbatches)
    # batch 0 = discovery/seed (cold): every group registers
    region.write(batches[0])
    db.flow_engine.on_write("src", batches[0]["ts"], batches[0],
                            appendable=region.last_write_appendable)
    db.flow_engine.run_all()
    per_batch = []
    t0 = time.perf_counter()
    for b in batches[1:]:
        region.write(b)
        tb = time.perf_counter()
        db.flow_engine.on_write("src", b["ts"], b,
                                appendable=region.last_write_appendable)
        db.flow_engine.run_all()
        per_batch.append(time.perf_counter() - tb)
    wall = time.perf_counter() - t0
    warm_rows = rows * (nbatches - 1)
    folded = sum(per_batch)
    # median batch = the steady state (a pow2 state-regrow + recompile
    # lands in one batch per window-capacity doubling and amortizes out
    # over a long-lived stream)
    med = sorted(per_batch)[len(per_batch) // 2] if per_batch else None
    out = {
        "rows_per_s_fold": round(rows / med, 1) if med else None,
        "rows_per_s_fold_incl_growth": round(warm_rows / folded, 1)
        if folded else None,
        "rows_per_s_wall": round(warm_rows / wall, 1),
        "fold_s_batches": [round(x, 3) for x in per_batch],
        "tick_ms_mean": _tick_stats("device" if device else "streaming"),
    }
    if device and db.flow_runtime is not None:
        rt = db.flow_runtime
        task = db.flow_engine.flows["bf"]
        out["fold_dispatches"] = rt.fold_dispatches
        out["reseeds"] = rt.reseeds
        out["fallbacks"] = rt.fallbacks
        out["state_bytes"] = db.flow_engine.state_bytes(task)
        out["device"] = task.device_state is not None
    checksum = db.sql(
        "SELECT count(*), sum(s), sum(c), sum(mn), sum(mx) FROM agg").rows[0]
    out["sink_checksum"] = [float(x) for x in checksum]
    db.close()
    return out


def _parity_check(groups: int = 500, rows: int = 4000, nbatches: int = 3):
    sinks = []
    for device in (True, False):
        db = _mk_db(device)
        region = db._region_of("src")
        for b in _batches(groups, rows, nbatches, seed=13):
            region.write(b)
            db.flow_engine.on_write("src", b["ts"], b,
                                    appendable=region.last_write_appendable)
            db.flow_engine.run_all()
        sinks.append(db.sql(
            "SELECT w, h, s, c, a, mn, mx FROM agg ORDER BY w, h").rows)
        db.close()
    return sinks[0] == sinks[1]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--groups", type=int, default=100_000)
    ap.add_argument("--rows", type=int, default=200_000)
    ap.add_argument("--batches", type=int, default=4)
    ap.add_argument("--host-batches", type=int, default=2)
    ap.add_argument("--out", default="BENCH_r14.json")
    args = ap.parse_args()

    import jax

    from greptimedb_tpu.compile.xla_cache import configure_xla_cache

    configure_xla_cache()
    backend = jax.default_backend()
    print(f"backend={backend} groups={args.groups} rows/batch={args.rows}")

    parity_ok = _parity_check()
    print(f"parity_ok={parity_ok}")

    print("device side ...")
    dev = _run_side(True, args.groups, args.rows, args.batches)
    print(f"  device fold: {dev['rows_per_s_fold']} rows/s "
          f"({dev.get('fold_dispatches')} dispatches, "
          f"{dev.get('reseeds')} reseeds)")
    print("host side ...")
    host = _run_side(False, args.groups, args.rows,
                     max(2, args.host_batches))
    print(f"  host fold: {host['rows_per_s_fold']} rows/s")

    speedup = None
    if dev["rows_per_s_fold"] and host["rows_per_s_fold"]:
        speedup = round(dev["rows_per_s_fold"] / host["rows_per_s_fold"], 2)
    result = {
        "bench": "flow_fold",
        "backend": backend,
        "groups": args.groups,
        "rows_per_batch": args.rows,
        "parity_ok": parity_ok,
        "device": dev,
        "host": host,
        "speedup_fold": speedup,
        "checksum_match": dev["sink_checksum"][:3] == host["sink_checksum"][:3]
        if args.batches == max(2, args.host_batches) else None,
    }
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
    print(json.dumps({k: v for k, v in result.items()
                      if k not in ("device", "host")}, indent=2))
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
