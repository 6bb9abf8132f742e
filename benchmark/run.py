#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json over the served HTTP path.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one client.  The steps, in order: (1) find the device and
fail unless it is a TPU with the cell's number of chips; (2) place
JAX's compilation cache in ``<checkout>/.jax_cache``; (3) start
``GreptimeDB`` + ``HttpServer`` as ``cli.py cmd_standalone`` does, in a
fresh data home under the temporary directory; (4) make the data from
``--seed`` and load it over ``POST /v1/arrow/write``; (5) count the rows
back; (6) send one request of every entry of the traffic mix, which builds
the resident state and loads or compiles every program;
(7) read ``/metrics``; (8) the window: the main thread sends the mix's
requests one after another for ``--seconds`` seconds; (9) read
``/metrics`` again; (10) compare every reply of the window, and of step
6, with the plain reference; (11) print the result line.  ``setup_s`` is 1-7.

Everything that belongs to one configuration, traffic mix, query family
or per-layer metric is a file this program finds by name (README.md);
no cell, configuration or metric is named here.

``--rehearse`` runs on whatever platform JAX has, at the
configuration's ``rehearsal`` size, and reports ``"correct": false``: a
rehearsal is never a result.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import http.client  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import socket  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import types  # noqa: E402
import urllib.parse  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_START_S = 2.0   # the traced slice opens this long into the window
TRACE_SLICE_S = 4.0   # and lasts this long
REQUEST_SPAN = "bench_request"
NO_ANSWER = 1e30   # the error of a value that is not there (JSON has no inf)


def say(phase: str, **counted) -> None:
    print(json.dumps({"phase": phase, **counted}), flush=True)


# ---------------------------------------------------------------------------
# files found by name
# ---------------------------------------------------------------------------

def load_module(kind: str, name: str) -> types.ModuleType:
    path = os.path.join(HERE, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    if spec is None or not os.path.exists(path):
        raise SystemExit(f"benchmark: no {kind}/{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(bench: dict, name: str) -> tuple[dict, dict, dict]:
    """(workload entry, configuration file, traffic file)."""
    for w in bench["workloads"]:
        if w["name"] == name:
            break
    else:
        raise SystemExit(f"benchmark: BENCHMARK.json has no workload {name!r}")
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = load_json(ROOT, conf["file"])
    traffic = load_json(HERE, "traffic", f"{w['traffic']}.json")
    if traffic.get("clients") != 1:
        raise SystemExit("benchmark: this harness drives one client; "
                         f"{w['traffic']} asks for {traffic.get('clients')}")
    return w, config, traffic


def new_cell(config: dict, rehearse: bool, seed: int | None = None):
    """What a family's functions take: the dataset module, its parameters,
    the data (made here when ``seed`` is given) and a ``cache`` dict."""
    ds = load_module("datasets", config["dataset"])
    params = dict(config["params"])
    if rehearse:
        params.update(config["rehearsal"])
    data = None if seed is None else ds.generate(seed, params)
    return types.SimpleNamespace(ds=ds, params=params, data=data, cache={})


def metrics_of(bench: dict, group: str, cell: str) -> list[dict]:
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


# ---------------------------------------------------------------------------
# the wire
# ---------------------------------------------------------------------------

class Client:
    """One keep-alive connection, as a TSBS worker holds."""

    def __init__(self, port: int):
        self.port = port
        self.conn: http.client.HTTPConnection | None = None

    def _connect(self) -> http.client.HTTPConnection:
        conn = http.client.HTTPConnection("127.0.0.1", self.port, timeout=1100)
        conn.connect()
        conn.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        return conn

    def send(self, method: str, path: str, body: bytes | None = None,
             headers: dict | None = None) -> tuple[int, bytes]:
        if self.conn is None:
            self.conn = self._connect()
        try:
            self.conn.request(method, path, body=body, headers=headers or {})
            r = self.conn.getresponse()
            return r.status, r.read()
        except (OSError, http.client.HTTPException):
            self.close()
            raise

    def close(self) -> None:
        if self.conn is not None:
            self.conn.close()
            self.conn = None

    def sql(self, q: str) -> list[list]:
        status, reply = self.send(
            "POST", "/v1/sql", urllib.parse.urlencode({"sql": q}).encode(),
            {"Content-Type": "application/x-www-form-urlencoded"})
        body = json.loads(reply)
        if status != 200 or body.get("code") != 0:
            raise RuntimeError(f"sql failed ({status}): {str(body)[:500]}")
        out = body["output"][0]
        return out["records"]["rows"] if "records" in out else []

    def arrow_write(self, table: str, body: bytes) -> int:
        status, reply = self.send("POST", f"/v1/arrow/write?table={table}",
                                  body)
        if status != 200:
            raise RuntimeError(f"arrow write answered {status}: {reply[:500]!r}")
        return json.loads(reply)["rows"]

    def metrics(self) -> dict[str, float]:
        """``GET /metrics`` as {``name{labels}``: value}."""
        status, reply = self.send("GET", "/metrics")
        if status != 200:
            raise RuntimeError(f"/metrics answered {status}")
        out = {}
        for line in reply.decode().splitlines():
            if line and not line.startswith("#"):
                key, _, val = line.rpartition(" ")
                out[key] = float(val)
        return out


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


# ---------------------------------------------------------------------------
# the comparison that decides `correct`
# ---------------------------------------------------------------------------

def scaled_error(got: np.ndarray, ref: np.ndarray, scale: float) -> float:
    """Worst |got - ref| / max(|ref|, scale); NO_ANSWER where a value is
    missing or not a number on one side only."""
    if got.shape != ref.shape:
        return NO_ANSWER
    if got.size == 0:
        return 0.0
    nan_g, nan_r = np.isnan(got), np.isnan(ref)
    if (nan_g != nan_r).any():
        return NO_ANSWER
    err = np.abs(got - ref) / np.maximum(np.abs(ref), scale)
    return float(np.nanmax(np.where(nan_r, 0.0, err)))


def judge(family, cell, req: dict, status: int | None, reply: bytes | None,
          answer=None) -> tuple[str, float]:
    """('ok' | 'failed' | 'wrong_keys', error) for one reply.  ``answer``
    stands in for the parsed reply (the control puts the lowered
    reference there)."""
    if answer is None:
        if status != 200 or reply is None:
            return "failed", NO_ANSWER
        try:
            answer = family.parse(req, reply)
        except (ValueError, KeyError, IndexError, TypeError):
            return "failed", NO_ANSWER
    keys, vals = answer
    ref_keys, ref_vals = family.reference(cell, req)
    if keys.shape != ref_keys.shape or not np.array_equal(keys, ref_keys):
        return "wrong_keys", NO_ANSWER
    return "ok", scaled_error(vals, ref_vals, family.SCALE[req["class"]])


class Checks:
    """Each number compared, beside its limit."""

    def __init__(self):
        self.values: dict[str, float] = {}
        self.limits: dict[str, float] = {}

    def worst(self, name: str, value: float, limit: float) -> None:
        self.limits[name] = limit
        self.values[name] = max(self.values.get(name, 0.0), value)

    def count(self, name: str, by: int = 1) -> None:
        self.limits[name] = 0
        self.values[name] = self.values.get(name, 0) + by

    def passed(self) -> bool:
        return all(v <= self.limits[k] for k, v in self.values.items())

    def table(self) -> dict:
        return {k: {"value": v, "limit": self.limits[k]}
                for k, v in self.values.items()}


def judge_log(family, cell, log: list[dict], checks: Checks) -> int:
    """Compare every logged reply; returns how many failed or were wrong."""
    bad = 0
    for name in ("replies_failed", "replies_wrong_keys"):
        checks.count(name, 0)
    for rec in log:
        verdict, err = judge(family, cell, rec["req"], rec["status"],
                             rec["reply"])
        cls = rec["req"]["class"]
        if verdict == "ok":
            checks.worst(f"max_err.{cls}", err, family.LIMITS[cls])
            bad += err > family.LIMITS[cls]
        else:
            checks.count(f"replies_{verdict}")
            bad += 1
    return bad


# ---------------------------------------------------------------------------
# traffic: one general generator over the mix's entries
# ---------------------------------------------------------------------------

class Traffic:
    """Round-robin over the mix's ``classes``; what each request asks
    for is drawn from the seed, in order."""

    def __init__(self, family, cell, mix: dict, seed: int, stream: int):
        self.family, self.cell, self.mix = family, cell, mix
        self.rng = np.random.default_rng([seed, stream])
        self.sent = 0

    def next(self) -> dict:
        entries = self.mix["classes"]
        entry = entries[self.sent % len(entries)]
        self.sent += 1
        req = self.family.request(self.cell, self.mix, entry, self.rng)
        req["entry"] = entry["name"]
        return req

    def warm_up(self) -> list[dict]:
        """One request of every entry of the mix."""
        return [self.next() for _ in self.mix["classes"]]


def exchange(client: Client, req: dict) -> dict:
    t0 = time.perf_counter()
    try:
        status, reply = client.send(req["method"], req["path"],
                                    req.get("body"), req.get("headers"))
    except (OSError, http.client.HTTPException) as e:
        status, reply = None, repr(e).encode()
    t1 = time.perf_counter()
    return {"req": req, "t_send": t0, "latency": t1 - t0, "status": status,
            "reply": reply, "traced": False}


def window(client: Client, traffic: Traffic, seconds: float,
           trace_dir: str | None) -> tuple[list[dict], float]:
    """Step 8.  With ``trace_dir`` a profiler trace covers a slice of it;
    each request of the slice sits inside a ``bench_request`` span so the
    reduction finds the slice on the trace's own clock."""
    import jax

    log: list[dict] = []
    tracing = "no" if trace_dir is None else "due"
    t_open = time.perf_counter()
    while True:
        now = time.perf_counter() - t_open
        if now >= seconds:
            break
        if tracing == "due" and now >= min(TRACE_START_S, seconds / 4):
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            tracing, t_trace = "on", time.perf_counter()
        elif tracing == "on" and (
                time.perf_counter() - t_trace >= min(TRACE_SLICE_S,
                                                     seconds / 3)):
            jax.profiler.stop_trace()
            tracing = "done"
        req = traffic.next()
        if tracing == "on":
            with jax.profiler.TraceAnnotation(
                    f"{REQUEST_SPAN}:{req['entry']}"):
                rec = exchange(client, req)
            rec["traced"] = True
        else:
            rec = exchange(client, req)
        log.append(rec)
    elapsed = time.perf_counter() - t_open
    if tracing == "on":
        jax.profiler.stop_trace()
    return log, elapsed


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             rehearse: bool = False, need_tpu: bool = True,
             keep_trace: str | None = None) -> dict:
    """Steps 1-11; returns the result line as a dict, ``correct`` as the
    comparison found it.  ``rehearse`` takes the configuration's
    rehearsal size and skips the look for a chip; ``need_tpu`` False
    skips only that look (the tests under tests/ drive the rest of a run
    so)."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)  # the program under test
    bench = load_json(ROOT, "BENCHMARK.json")
    entry, config, traffic_file = load_cell(bench, workload)
    phases: dict[str, float] = {}

    # ---- 1, 2: device, compile cache ---------------------------------
    t0 = time.perf_counter()
    family = load_module("queries", traffic_file["family"])
    cell = new_cell(config, rehearse)
    ds, params = cell.ds, cell.params
    made: dict = {}

    def make_data():
        t = time.perf_counter()
        made["data"] = ds.generate(seed, params)
        made["seconds"] = time.perf_counter() - t

    # numpy releases the GIL: the data is made while the device starts
    maker = threading.Thread(target=make_data, name="bench-generate",
                             daemon=True)
    maker.start()

    import jax

    from greptimedb_tpu import native
    from greptimedb_tpu.compile.xla_cache import (configure_xla_cache,
                                                  xla_cache_stats)

    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if need_tpu and not rehearse:
        if dev["platform"] != "tpu":
            raise SystemExit(f"benchmark: JAX found {dev}, not a TPU "
                             "(--rehearse runs without one)")
        if len(devices) != entry["chips"]:
            raise SystemExit(f"benchmark: {len(devices)} chips visible, "
                             f"{workload} needs {entry['chips']}")
    peaks = load_json(HERE, "peaks.json")
    if dev["kind"] not in peaks and dev["platform"] == "tpu":
        raise SystemExit(f"benchmark: no peaks for device kind "
                         f"{dev['kind']!r} in peaks.json")
    cache_dir = configure_xla_cache()
    native_built = native.build()
    phases["device_s"] = time.perf_counter() - t0
    say("device", seconds=phases["device_s"], **dev, xla_cache_dir=cache_dir,
        native_built=native_built)

    checks = Checks()
    home = tempfile.mkdtemp(prefix="bench_")
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    db, srv = start_server(home)
    client = Client(srv.port)
    try:
        # ---- 4: data, table, load ------------------------------------
        maker.join()
        cell.data = made["data"]
        phases["generate_s"] = made["seconds"]
        t0 = time.perf_counter()
        for stmt in ds.ddl(params):
            client.sql(stmt)
        acked = sent_bytes = 0
        for table, body, _n in ds.arrow_bodies(cell.data, params):
            sent_bytes += len(body)
            acked += client.arrow_write(table, body)
        phases["load_s"] = time.perf_counter() - t0
        # ---- 5: count back -------------------------------------------
        t0 = time.perf_counter()
        counted = client.sql(ds.count_sql(params))[0][0]
        phases["count_s"] = time.perf_counter() - t0
        checks.count("rows_not_acked", abs(ds.rows(params) - acked))
        checks.count("rows_not_counted_back", abs(acked - counted))
        say("load", seconds=phases["load_s"], rows=acked, bytes=sent_bytes,
            counted_back=counted, generate_seconds=phases["generate_s"])
        # ---- 6: first queries ----------------------------------------
        t0 = time.perf_counter()
        warm = Traffic(family, cell, traffic_file, seed, stream=2)
        warm_log = [exchange(client, req) for req in warm.warm_up()]
        phases["first_queries_s"] = time.perf_counter() - t0
        say("first_queries", seconds=phases["first_queries_s"], each=[
            [r["req"]["entry"], round(r["latency"], 4)] for r in warm_log])
        # ---- 7: /metrics ---------------------------------------------
        before = client.metrics()
        xla_before = xla_cache_stats()
        setup_s = time.perf_counter() - _T_START
        # ---- 8: the window -------------------------------------------
        traffic = Traffic(family, cell, traffic_file, seed, stream=1)
        log, elapsed = window(client, traffic, seconds, trace_dir)
        # ---- 9: /metrics again ---------------------------------------
        after = client.metrics()
        xla_after = xla_cache_stats()
        memory = [d.memory_stats() or {} for d in devices]
        memory_peak = max(m.get("peak_bytes_in_use", 0) for m in memory)
        from greptimedb_tpu.query.physical import DISPATCH_STATS

        say("window", seconds=elapsed, requests=len(log),
            dispatch=dict(DISPATCH_STATS), xla_cache=xla_after,
            bytes_in_use=memory[0].get("bytes_in_use"))
    finally:
        client.close()
        srv.stop()
        db.close(flush=True)
        shutil.rmtree(home, ignore_errors=True)

    # ---- 10: every reply against the plain reference -------------------
    t0 = time.perf_counter()
    gc.disable()  # millions of parsed rows: the collector would walk them all
    try:
        judge_log(family, cell, warm_log, checks)
        bad = judge_log(family, cell, log, checks)
    finally:
        gc.enable()
    phases["check_s"] = time.perf_counter() - t0

    # ---- the numbers: one reader a metric, found by its name ------------
    reduced = None
    if trace:
        t0 = time.perf_counter()
        reduced = load_module(".", "trace_reduce").reduce_dir(
            trace_dir, REQUEST_SPAN)
        phases["trace_reduce_s"] = time.perf_counter() - t0
        if keep_trace:
            shutil.copytree(trace_dir, keep_trace, dirs_exist_ok=True)
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = {
        "log": log, "elapsed": elapsed, "setup_s": setup_s,
        "metrics_before": before, "metrics_after": after,
        "xla_before": xla_before, "xla_after": xla_after, "phases": phases,
        "rows_acked": acked, "trace": reduced, "peaks": peaks.get(dev["kind"]),
        "needed_bytes": lambda req: family.needed_bytes(cell, req),
    }
    ctx["read"] = lambda name: load_module("layer_metrics", name).read(ctx)

    def read_group(group: str, folder: str) -> dict:
        out = {}
        for m in metrics_of(bench, group, workload):
            value = load_module(folder, m["name"]).read(ctx)
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        return out

    layers = read_group("per_layer", "layer_metrics")
    end_to_end = read_group("end_to_end", "end_to_end")
    by_entry: dict[str, list[float]] = {}
    for r in log:
        by_entry.setdefault(r["req"]["entry"], []).append(1e3 * r["latency"])
    say("entries", **{k: {"n": len(v), "median_ms": float(np.median(v)),
                          "max_ms": max(v)} for k, v in by_entry.items()})
    lat = np.sort([1e3 * r["latency"] for r in log])
    say("percentiles", **{f"p{q}": float(lat[max(0, -(-q * len(lat) // 100) - 1)])
                          for q in (50, 90, 95, 99)}, max=float(lat[-1]))
    # each third of the window alone: thirds that agree where runs differ
    # say the noise is between processes, and a longer window buys nothing
    thirds = [[r["latency"] for r in log
               if i <= 3 * (r["t_send"] - log[0]["t_send"]) / elapsed < i + 1]
              for i in range(3)]
    say("thirds", requests=[len(t) for t in thirds],
        median_ms=[1e3 * float(np.median(t)) if t else None for t in thirds])
    say("phases", **phases)
    say("layers", **{k: v["value"] for k, v in layers.items()})
    say("end_to_end", **{k: v["value"] for k, v in end_to_end.items()})

    device = {**dev, "memory_peak_bytes": int(memory_peak)}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    metrics = layers if trace else end_to_end
    result = {"correct": checks.passed() and len(log) > 0,
              "attempted": len(log), "failed": bad, "metrics": metrics,
              "device": device}
    if reduced is not None:
        result["breakdown"] = reduced["breakdown"]
    result["checks"] = checks.table()
    return result


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="any platform, rehearsal size; never correct")
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="with --trace 1, copy the profiler's files here "
                         "for a look by hand")
    args = ap.parse_args(argv)
    if args.seconds <= 0 or args.seed < 0:
        ap.error("--seconds must be positive and --seed not negative")
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), rehearse=args.rehearse,
                      keep_trace=args.keep_trace)
    if args.rehearse:  # a rehearsal is never a result
        checks = result.pop("checks")
        result.update(rehearsal_checks_passed=result["correct"],
                      correct=False, checks=checks)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct = {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
