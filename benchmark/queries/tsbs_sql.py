"""TSBS devops queries over the ``cpu`` table, through ``POST /v1/sql``.

Three classes, each with its request, its plain numpy reference and
its ``needed_bytes``; no jax, nothing of the program under test.

  single_groupby  max of the first ``metrics`` fields over ``hosts``
                  random hosts, by minute, for ``hours`` hours
                  (TSBS single-groupby-M-H-T)
  cpu_max_all     max of all ten fields over ``hosts`` random hosts, by
                  hour, for 8 hours (TSBS cpu-max-all-H)
  double_groupby  avg of the first ``metrics`` fields by host and hour
                  over the newest 12 hours (TSBS double-groupby-N)

TSBS takes the first N of the ten metrics (``GetCPUMetricsSlice``) and
draws the hosts (distinct, from all of them) and the window's start at
random for every query; with 12 h of data kept the window starts on a
whole hour.  Here both come from the run's seed.  ``cell`` carries
``ds`` (the dataset module), ``params`` (its parameters), ``data`` and a
``cache`` dict.

A reference returns ``(keys, values)``: int64 keys [n, k] in sorted
order and float64 values [n, m]; ``parse`` turns a reply into the same.
With ``lower`` (a rounding function, see lowprec.py) the reference is
the control: inputs and result rounded, sums kept in float32.
"""

from __future__ import annotations

import json
import urllib.parse

import numpy as np

PATH = "/v1/sql"
# |got - ref| / max(|ref|, SCALE): usage values live in [0, 100]
SCALE = {"single_groupby": 1.0, "cpu_max_all": 1.0, "double_groupby": 1.0}
# limits on that error, set from chip readings (PERF.md section 2)
LIMITS = {"single_groupby": 1e-5, "cpu_max_all": 1e-5,
          "double_groupby": 5e-5}
_MAX_ALL_HOURS = 8
_DOUBLE_HOURS = 12


def _window(cell, hours: int, rng) -> tuple[int, int]:
    per_hour = cell.ds.steps_per_hour(cell.params)
    have = cell.params["hours"]
    hours = min(hours, have)
    k0 = int(rng.integers(have - hours + 1)) * per_hour
    return k0, k0 + hours * per_hour


def _where(cell, k0: int, k1: int) -> str:
    step_ms = cell.params["interval_s"] * 1000
    return f"ts >= {cell.ds.T0 + k0 * step_ms} AND ts < {cell.ds.T0 + k1 * step_ms}"


def _hosts(cell, n: int, rng) -> list[int]:
    have = cell.params["hosts"]
    return rng.choice(have, size=min(n, have), replace=False).tolist()


def request(cell, mix: dict, entry: dict, rng):
    fields = cell.ds.FIELDS
    cls, p = entry["class"], entry.get("params", {})
    if cls in ("single_groupby", "cpu_max_all"):
        hours, m, unit = ((p["hours"], p["metrics"], "minute")
                          if cls == "single_groupby" else
                          (_MAX_ALL_HOURS, len(fields), "hour"))
        k0, k1 = _window(cell, hours, rng)
        hs = _hosts(cell, p["hosts"], rng)
        aggs = ", ".join(f"max({f})" for f in fields[:m])
        names = ", ".join(f"'host_{h}'" for h in hs)
        sql = (f"SELECT date_trunc('{unit}', ts) AS {unit}, {aggs} FROM cpu "
               f"WHERE hostname IN ({names}) AND {_where(cell, k0, k1)} "
               f"GROUP BY {unit} ORDER BY {unit}")
    elif cls == "double_groupby":
        k0, k1 = _window(cell, _DOUBLE_HOURS, rng)
        hs = None  # every host
        m = p["metrics"]
        aggs = ", ".join(f"avg({f})" for f in fields[:m])
        sql = (f"SELECT hostname, date_trunc('hour', ts) AS hour, {aggs} "
               f"FROM cpu WHERE {_where(cell, k0, k1)} "
               "GROUP BY hostname, hour")
    else:
        raise ValueError(f"tsbs_sql has no class {cls!r}")
    return {"class": cls, "method": "POST", "path": PATH, "route": PATH,
            "body": urllib.parse.urlencode({"sql": sql}).encode(),
            "headers": {"Content-Type": "application/x-www-form-urlencoded"},
            "k0": k0, "k1": k1, "hosts": hs, "metrics": m}


def _bucket_steps(cell, cls: str) -> int:
    per_hour = cell.ds.steps_per_hour(cell.params)
    return per_hour // 60 if cls == "single_groupby" else per_hour


def reference(cell, req: dict, lower=None):
    values = cell.data["values"]
    cls, k0, k1, m = req["class"], req["k0"], req["k1"], req["metrics"]
    b = _bucket_steps(cell, cls)
    n_b = (k1 - k0) // b
    step_ms = cell.params["interval_s"] * 1000
    ts = cell.ds.T0 + (k0 + b * np.arange(n_b, dtype=np.int64)) * step_ms
    if cls == "double_groupby":
        hosts = values.shape[1]
        # by (host, hour) for all ten fields once; requests take columns
        key = ("double_groupby", k0, k1, lower)
        if key not in cell.cache:
            v = values[k0:k1]
            if lower is not None:
                v = lower(v)
            v = v.reshape(n_b, b, hosts, -1)
            mean = (v.mean(axis=1) if lower is None else
                    lower(v.mean(axis=1, dtype=np.float32)))
            # [hour, host, f] -> rows sorted by (host, hour)
            cell.cache[key] = np.ascontiguousarray(
                mean.transpose(1, 0, 2).reshape(hosts * n_b, -1), np.float64)
        keys = np.stack([np.repeat(np.arange(hosts, dtype=np.int64), n_b),
                         np.tile(ts, hosts)], axis=1)
        return keys, cell.cache[key][:, :m]
    v = values[k0:k1][:, req["hosts"]][:, :, :m]
    if lower is not None:
        v = lower(v)  # a max of rounded values is a rounded value
    out = v.reshape(n_b, b * len(req["hosts"]), m).max(axis=1)
    return ts[:, None], out.astype(np.float64)


def parse(req: dict, reply: bytes):
    body = json.loads(reply)
    if body.get("code") != 0:
        raise ValueError(f"sql failed: {str(body)[:300]}")
    rows = body["output"][0]["records"]["rows"]
    if req["class"] == "double_groupby":
        keys = np.array([[int(r[0][5:]), r[1]] for r in rows], dtype=np.int64)
        vals = np.array([r[2:] for r in rows], dtype=np.float64)
        order = np.lexsort((keys[:, 1], keys[:, 0])) if len(rows) else []
        return keys[order], vals[order]
    keys = np.array([[r[0]] for r in rows], dtype=np.int64)
    return keys, np.array([r[1:] for r in rows], dtype=np.float64)


def needed_bytes(cell, req: dict) -> int:
    """The selected input read once at the query's own bucket width (4 B
    a (series, bucket, field) cell) plus the result written once (8 B a
    key or timestamp, 4 B a value)."""
    b = _bucket_steps(cell, req["class"])
    buckets = (req["k1"] - req["k0"]) // b
    m = req["metrics"]
    if req["class"] == "double_groupby":
        series = cell.params["hosts"]
        out_rows, key_bytes = series * buckets, 16
    else:
        series = len(req["hosts"])
        out_rows, key_bytes = buckets, 8
    return 4 * series * buckets * m + out_rows * (key_bytes + 4 * m)
