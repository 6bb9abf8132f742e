"""The "CPU Usage" panel of kubernetes-mixin's ``k8s-resources-cluster``
dashboard over the raw cAdvisor counter, as its recording rule reads it:

    sum by (namespace)(rate(container_cpu_usage_seconds_total{job="cadvisor",image!=""}[5m]))

through ``GET /v1/prometheus/api/v1/query_range``.  What the README's
table would say of this family:

| class | parameters | reply |
| --- | --- | --- |
| ``namespace_cpu`` | none of its own: 30 min at a 30 s step (61 steps; half the data where there is less than an hour), the end drawn from the seed on a scrape boundary inside the last 25 min (50 ends) | one series a namespace, 61 points each |

The matchers and the grouping are the same in every request, so one
program serves them all; only ``start`` and ``end`` move.

The reference is Prometheus's ``extrapolatedRate`` (counter semantics,
window (t - range, t]) in numpy over the generated samples, with counter
resets and with each series' own first and last sample (a series that
starts late, ends early, or has one sample in a window), then a plain
sum of the series of each namespace; no jax, nothing of the program
under test, nothing of a sibling family.  Stored DOUBLEs compute in
float32 on the device, so the reference reads the samples at float32 and
then works in float64.  With ``lower`` (lowprec.py) it is the control:
samples and result rounded, arithmetic in float32.

**Memoised, and still the plain reference.**  The step is the scrape
interval and every end lies on a scrape boundary, so every evaluation
time of every request is one of the data's scrape times.  ``_table``
therefore evaluates the same function at every scrape time once a run
([namespaces, steps], a second of numpy at 63,000 series) and
``reference`` slices the request's 61 columns out of it: the same
arithmetic on the same samples as evaluating the request alone
(``tests/test_k8s_references.py`` holds the slice to a loop over samples),
done once where a window of a thousand replies would do it a thousand
times.
"""

from __future__ import annotations

import json
import urllib.parse

import numpy as np

ROUTE = "/v1/prometheus/api/v1/query_range"
RANGE_S = 300
_SPAN_S = 1800
_END_WITHIN_S = 1500
# |got - ref| / max(|ref|, SCALE): a namespace's rate is cores in use,
# from 0.1 for the smallest namespaces to hundreds for the largest
SCALE = {"namespace_cpu": 1e-3}
# limit on that error, set from chip readings (PERF.md section 2): sound
# runs read up to 5.5e-6 (an f32 sum of up to 10,590 rates a namespace),
# the bfloat16 control at least 0.85
LIMITS = {"namespace_cpu": 1e-4}


def _span(cell) -> tuple[int, int]:
    """(seconds a query spans, seconds before the data's end in which it
    may end), both whole scrape intervals."""
    total = cell.params["hours"] * 3600
    span = min(_SPAN_S, total // 2)
    return span, min(_END_WITHIN_S, total - span - RANGE_S)


def query(cell) -> str:
    return (f'sum by (namespace)(rate({cell.ds.TABLE}'
            f'{{job="cadvisor",image!=""}}[5m]))')


def request(cell, mix: dict, entry: dict, rng):
    if entry["class"] != "namespace_cpu":
        raise ValueError(f"k8s_rate has no class {entry['class']!r}")
    ds, interval = cell.ds, cell.params["interval_s"]
    span, within = _span(cell)
    last = ds.steps(cell.params) - 1
    e = last - int(rng.integers(within // interval))
    end_s = ds.T0 // 1000 + e * interval
    qs = urllib.parse.urlencode({"query": query(cell), "start": end_s - span,
                                 "end": end_s, "step": interval})
    return {"class": "namespace_cpu", "method": "GET",
            "path": f"{ROUTE}?{qs}", "route": ROUTE,
            "start_s": end_s - span, "end_s": end_s}


def rate_at_scrapes(vals: np.ndarray, step_ms: int, range_ms: int,
                    dtype=np.float64) -> np.ndarray:
    """extrapolatedRate at every scrape time for samples every
    ``step_ms``; ``vals`` [steps, S] with NaN where a series has no
    sample; returns [S, steps], NaN where a window holds fewer than two
    samples of the series.  The window of scrape j is the scrapes in
    (j*step - range, j*step]."""
    n_steps, series = vals.shape
    vals = vals.astype(dtype)
    out = np.full((series, n_steps), np.nan, dtype=dtype)
    back = (range_ms - 1) // step_ms     # scrapes before j inside the window
    col = np.arange(series)
    for j in range(n_steps):
        w = vals[max(j - back, 0):j + 1]
        here = ~np.isnan(w)
        cnt = here.sum(axis=0)
        first_i = np.argmax(here, axis=0)
        last_i = len(w) - 1 - np.argmax(here[::-1], axis=0)
        first_v, last_v = w[first_i, col], w[last_i, col]
        # what every fall inside the window took away is added back
        fallen = np.zeros(series, dtype=dtype)
        prev = np.full(series, np.nan, dtype=dtype)
        for k in range(len(w)):
            fallen += np.where(here[k] & (w[k] < prev), prev, 0)
            prev = np.where(here[k], w[k], prev)
        delta = last_v - first_v + fallen
        t = j * step_ms
        first_t = (max(j - back, 0) + first_i) * step_ms
        last_t = (max(j - back, 0) + last_i) * step_ms
        sampled = ((last_t - first_t) / 1000.0).astype(dtype)
        with np.errstate(divide="ignore", invalid="ignore"):
            avg = sampled / (cnt - 1)
            to_start = ((first_t - (t - range_ms)) / 1000.0).astype(dtype)
            to_end = ((t - last_t) / 1000.0).astype(dtype)
            to_start = np.where(to_start >= avg * dtype(1.1), avg / 2,
                                to_start)
            to_end = np.where(to_end >= avg * dtype(1.1), avg / 2, to_end)
            to_zero = np.where(delta > 0, sampled * (first_v / delta), np.inf)
            start = np.minimum(to_start, to_zero)
            rate = (delta * (sampled + start + to_end) / sampled
                    / dtype(range_ms / 1000.0))
        out[:, j] = np.where(cnt >= 2, rate, np.nan)
    return out


def group_sum(per: np.ndarray, group: np.ndarray, groups: int) -> np.ndarray:
    """[groups, steps]: the sum over each group's series that have a value,
    NaN where none has; in ``per``'s own precision."""
    out = np.full((groups, per.shape[1]), np.nan, dtype=per.dtype)
    for g in range(groups):
        rows = per[group == g]
        has = ~np.isnan(rows)
        out[g] = np.where(has.any(axis=0),
                          np.where(has, rows, 0).sum(axis=0), np.nan)
    return out


def _table(cell, lower=None) -> np.ndarray:
    """[namespaces, steps] at every scrape time, once a run."""
    key = "k8s_rate.table" if lower is None else "k8s_rate.table.lower"
    if key not in cell.cache:
        p, data = cell.params, cell.data
        vals = data["values"][:, data["matched"]]
        group = data["namespace"][data["matched"]]
        step_ms = p["interval_s"] * 1000
        if lower is None:
            per = rate_at_scrapes(vals.astype(np.float32), step_ms,
                                  RANGE_S * 1000)
        else:
            per = rate_at_scrapes(lower(vals), step_ms, RANGE_S * 1000,
                                  dtype=np.float32)
        table = group_sum(per, group, p["namespaces"])
        if lower is not None:
            table = lower(table)   # NaN stays NaN
        cell.cache[key] = table.astype(np.float64)
    return cell.cache[key]


def _steps(cell, req: dict) -> np.ndarray:
    interval = cell.params["interval_s"]
    t0_s = cell.ds.T0 // 1000
    return np.arange((req["start_s"] - t0_s) // interval,
                     (req["end_s"] - t0_s) // interval + 1)


def reference(cell, req: dict, lower=None):
    at = _steps(cell, req)
    by_ns = _table(cell, lower)[:, at]
    eval_ms = cell.ds.T0 + at.astype(np.int64) * (
        cell.params["interval_s"] * 1000)
    namespaces = by_ns.shape[0]
    keys = np.stack([np.repeat(np.arange(namespaces, dtype=np.int64), len(at)),
                     np.tile(eval_ms, namespaces)], axis=1)
    vals_out = by_ns.reshape(-1, 1)
    keep = ~np.isnan(vals_out[:, 0])  # Prometheus leaves such points out
    return keys[keep], vals_out[keep]


def parse(req: dict, reply: bytes):
    body = json.loads(reply)
    if body.get("status") != "success":
        raise ValueError(f"promql failed: {str(body)[:300]}")
    keys, vals = [], []
    for series in body["data"]["result"]:
        if set(series["metric"]) != {"namespace"}:
            raise ValueError(f"labels {sorted(series['metric'])}")
        ns = int(series["metric"]["namespace"].split("-")[1])
        for t, v in series["values"]:
            keys.append((ns, int(round(float(t) * 1000))))
            vals.append(float(v))
    keys = np.array(keys, dtype=np.int64).reshape(-1, 2)
    vals = np.array(vals, dtype=np.float64).reshape(-1, 1)
    order = np.lexsort((keys[:, 1], keys[:, 0]))
    return keys[order], vals[order]


def needed_bytes(cell, req: dict) -> int:
    """A range vector has no bucket: 12 B (i64 timestamp, f32 value) for
    each sample the matched series have in (start - range, end], plus the
    result written once (8 B a timestamp, 4 B a value)."""
    if "k8s_rate.samples" not in cell.cache:
        data = cell.data
        has = ~np.isnan(data["values"][:, data["matched"]])
        cell.cache["k8s_rate.samples"] = np.concatenate(
            [[0], np.cumsum(has.sum(axis=1))])
    upto = cell.cache["k8s_rate.samples"]
    at = _steps(cell, req)
    back = (RANGE_S - 1) // cell.params["interval_s"]
    samples = int(upto[at[-1] + 1] - upto[max(at[0] - back, 0)])
    return 12 * samples + 12 * cell.params["namespaces"] * len(at)
