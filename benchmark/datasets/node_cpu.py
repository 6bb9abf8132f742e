"""node_exporter's ``node_cpu_seconds_total``: one counter a (instance,
cpu, mode), scraped every ``interval_s`` seconds.

numpy and pyarrow only.  Per (instance, cpu) the eight modes' shares of
each second come from a seeded walk in logit space and sum to one
CPU-second a second; every node boots at the first sample, so counters
start at 0, only rise, and have no reset.

``params``: ``instances``, ``cpus``, ``hours``, ``interval_s``.
Series index = (instance * cpus + cpu) * 8 + mode.
"""

from __future__ import annotations

import io

import numpy as np

TABLE = "node_cpu_seconds_total"
MODES = ["idle", "iowait", "irq", "nice", "softirq", "steal", "system",
         "user"]
# typical shares of a lightly loaded node, as logits' starting point
_BASE = np.log(np.array([0.80, 0.02, 0.005, 0.005, 0.01, 0.005, 0.055, 0.10]))
T0 = 1704067200000  # 2024-01-01T00:00:00Z (ms)
_BODY_BYTES = 32 << 20
_ROW_BYTES = 28


def steps(params: dict) -> int:
    return params["hours"] * 3600 // params["interval_s"]


def n_series(params: dict) -> int:
    return params["instances"] * params["cpus"] * len(MODES)


def rows(params: dict) -> int:
    return steps(params) * n_series(params)


def generate(seed: int, params: dict) -> dict:
    """``values`` [steps, series] float64 counters in CPU-seconds."""
    rng = np.random.default_rng(seed)
    n_steps = steps(params)
    cores = params["instances"] * params["cpus"]
    logits = _BASE[None, None, :] + rng.normal(
        0, 0.5, size=(1, cores, len(MODES)))
    out = np.empty((n_steps, cores, len(MODES)))
    total = np.zeros((cores, len(MODES)))
    chunk = 360
    for s in range(0, n_steps, chunk):
        n = min(chunk, n_steps - s)
        walk = np.cumsum(rng.normal(0, 0.02, size=(n, cores, len(MODES))),
                         axis=0)
        lg = logits + walk
        logits = lg[-1:]
        e = np.exp(lg - lg.max(axis=2, keepdims=True))
        inc = e / e.sum(axis=2, keepdims=True) * params["interval_s"]
        out[s:s + n] = total[None] + np.cumsum(inc, axis=0)
        total = out[s + n - 1]
    out -= out[0]  # booted at the first sample
    return {"values": out.reshape(n_steps, -1)}


def ddl(params: dict) -> list[str]:
    return [f"CREATE TABLE {TABLE} (instance STRING, cpu STRING, mode STRING, "
            "ts TIMESTAMP(3) TIME INDEX, greptime_value DOUBLE, "
            "PRIMARY KEY (instance, cpu, mode))"]


def count_sql(params: dict) -> str:
    return f"SELECT count(*) FROM {TABLE}"


def instance_names(params: dict) -> list[str]:
    return [f"node-{i}:9100" for i in range(params["instances"])]


def arrow_bodies(data: dict, params: dict):
    """Yields (table, Arrow IPC stream bytes, rows) in time order."""
    import pyarrow as pa

    values = data["values"]
    n_steps, series = values.shape
    cpus, modes = params["cpus"], len(MODES)
    step_ms = params["interval_s"] * 1000
    idx = np.arange(series, dtype=np.int32)
    tags = {
        "instance": (idx // (cpus * modes), pa.array(instance_names(params))),
        "cpu": (idx // modes % cpus, pa.array([str(c) for c in range(cpus)])),
        "mode": (idx % modes, pa.array(MODES)),
    }
    per = max(1, _BODY_BYTES // (_ROW_BYTES * series))
    for s in range(0, n_steps, per):
        n = min(per, n_steps - s)
        cols = {name: pa.DictionaryArray.from_arrays(
            pa.array(np.tile(codes.astype(np.int32), n)), names)
            for name, (codes, names) in tags.items()}
        cols["ts"] = pa.array(np.repeat(
            T0 + np.arange(s, s + n, dtype=np.int64) * step_ms, series))
        cols["greptime_value"] = pa.array(values[s:s + n].reshape(-1))
        table = pa.table(cols)
        sink = io.BytesIO()
        with pa.ipc.new_stream(sink, table.schema) as w:
            w.write_table(table)
        yield TABLE, sink.getvalue(), n * series
