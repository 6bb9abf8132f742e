"""The API server's read and write latency SLIs, kubernetes-mixin's
``kube-apiserver-histogram.rules`` (the rule
``cluster_quantile:apiserver_request_sli_duration_seconds:histogram_quantile``,
plotted by the apiserver dashboard's "Read SLI - Duration" and "Write
SLI - Duration" panels), written without ``cluster`` (one cluster):

    histogram_quantile(0.99, sum by (le, resource) (rate(apiserver_request_sli_duration_seconds_bucket{job="apiserver",verb=~"LIST|GET",subresource!~"proxy|attach|log|exec|portforward"}[5m]))) > 0
    histogram_quantile(0.99, sum by (le, resource) (rate(apiserver_request_sli_duration_seconds_bucket{job="apiserver",verb=~"POST|PUT|PATCH|DELETE",subresource!~"proxy|attach|log|exec|portforward"}[5m]))) > 0

through ``GET /v1/prometheus/api/v1/query_range``.  What the README's
table would say of this family:

| class | parameters | reply |
| --- | --- | --- |
| ``read_p99`` | none of its own: 30 min at a 30 s step (61 steps; half the data where there is less than an hour), the end drawn from the seed on a scrape boundary inside the last 25 min (50 ends) | one series a resource, up to 61 points each |
| ``write_p99`` | the same with the write verbs | the same |

The matchers and the grouping are the same in every request of a class,
so one program serves it; only ``start`` and ``end`` move.

The reference, in numpy **float64 over the float64 samples that were
sent**: Prometheus's ``extrapolatedRate`` (counter semantics, window
(t - range, t], resets added back, each series' own first and last
sample), a plain float64 sum by (``le``, ``resource``), Prometheus's
``bucketQuantile`` (promql/quantile.go: buckets sorted by bound, counts
forced monotonic, NaN without ``+Inf``, under two buckets or with no
observations, the highest finite bound where the rank falls in ``+Inf``,
linear interpolation inside the bucket), and ``> 0``.  One rule more than
quantile.go, the program's stated one: a rank within ``RANK_TOLERANCE``
of the total above a bucket's count, with the next bucket empty, is a tie
with that count.  Counts are whole requests and every series of a window
shares its extrapolation, so q x total often equals a bucket's count
exactly; the answer then depends on the last bit of two sums, and with
an empty bucket next it jumps by a bucket's width.  The rule gives such
a tie the lower bucket's answer on every side; with the next bucket not
empty the answer is quantile.go's, which has no step there.  No jax,
nothing of the program under test, nothing of a sibling family.  With
``lower`` the *samples* are rounded through it before the difference and
everything else stays as it is:
``lower = F32`` is the control that stands for a float32 value column
(the busiest counters pass 2^24, where float32 has steps of 2 and more);
``control.py`` hands over the bfloat16 rounding of ``lowprec.py``.

**Memoised, and still the plain reference.**  The step is the scrape
interval and every end lies on a scrape boundary, so every evaluation
time of every request is one of the data's scrape times; ``_table``
evaluates the same function at every scrape time once a run and class
([resources, steps]) and ``reference`` slices the request's columns out
of it (``tests/test_k8s_apiserver_references.py`` holds the slice to a
loop over samples).

**A program without the bucket fold cannot run this family.**  Loading
the family beside a program asks it for ``promql.engine
.histogram_fold``, its ``bucketQuantile``, and exits with an error at
once where it is missing.  A ``histogram_quantile`` without it forces no
bucket counts monotonic, and ``rate`` over bucket counters needs that in
the windows after the data's restart, where each bucket extrapolates to
zero by its own first sample: such a program answers some seeds wrong
after minutes of load.  The reference itself uses nothing of the program.
"""

from __future__ import annotations

import importlib.util
import json
import math
import urllib.parse

import numpy as np

# a run puts the program on the path before it loads a family; the numpy
# control (control.py) loads this one with no program beside it
if importlib.util.find_spec("greptimedb_tpu") is not None:
    try:
        from greptimedb_tpu.promql.engine import histogram_fold  # noqa: F401
    except ImportError as e:
        raise SystemExit(
            "benchmark: k8s_apiserver_quantile needs the program's bucket "
            "fold (greptimedb_tpu.promql.engine.histogram_fold, "
            f"Prometheus's bucketQuantile); this program has none ({e})"
        ) from e

ROUTE = "/v1/prometheus/api/v1/query_range"
RANGE_S = 300
Q = 0.99
_SPAN_S = 1800
_END_WITHIN_S = 1500
CLASSES = {"read_p99": "LIST|GET", "write_p99": "POST|PUT|PATCH|DELETE"}
EXCLUDED = "proxy|attach|log|exec|portforward"
# the program's tie rule (promql/engine.py RANK_TOLERANCE), copied
RANK_TOLERANCE = 2.0 ** -18
# |got - ref| / max(|ref|, SCALE): a quantile in seconds, 0.01 to 60
SCALE = {"read_p99": 1e-3, "write_p99": 1e-3}
# limit on that error, set between two readings (PERF.md section 2 has
# them): sound runs, and a float32 value column (the F32 control)
LIMITS = {"read_p99": 1e-3, "write_p99": 1e-3}


def F32(x):
    """The control's ``lower``: samples as a float32 value column holds
    them."""
    return np.asarray(x).astype(np.float32)


def _matched(cell, cls: str) -> np.ndarray:
    if cls not in CLASSES:
        raise ValueError(f"k8s_apiserver_quantile has no class {cls!r}")
    return cell.data["read" if cls == "read_p99" else "write"]


def _span(cell) -> tuple[int, int]:
    """(seconds a query spans, seconds before the data's end in which it
    may end), both whole scrape intervals."""
    total = cell.ds.steps(cell.params) * cell.params["interval_s"]
    span = min(_SPAN_S, total // 2)
    return span, min(_END_WITHIN_S, total - span - RANGE_S)


def query(cell, cls: str) -> str:
    return (f"histogram_quantile({Q}, sum by (le, resource) "
            f"(rate({cell.ds.TABLE}{{job=\"apiserver\","
            f"verb=~\"{CLASSES[cls]}\",subresource!~\"{EXCLUDED}\"}}"
            f"[5m]))) > 0")


def request(cell, mix: dict, entry: dict, rng):
    cls = entry["class"]
    ds, interval = cell.ds, cell.params["interval_s"]
    span, within = _span(cell)
    last = ds.steps(cell.params) - 1
    e = last - int(rng.integers(within // interval))
    end_s = ds.T0 // 1000 + e * interval
    qs = urllib.parse.urlencode({"query": query(cell, cls),
                                 "start": end_s - span, "end": end_s,
                                 "step": interval})
    if "k8s_apiserver_quantile.names" not in cell.cache:
        cell.cache["k8s_apiserver_quantile.names"] = {
            r[2]: i for i, r in enumerate(ds.resources(cell.params))}
    return {"class": cls, "method": "GET", "path": f"{ROUTE}?{qs}",
            "route": ROUTE, "start_s": end_s - span, "end_s": end_s,
            "resources": cell.cache["k8s_apiserver_quantile.names"]}


def rate_at_scrapes(vals: np.ndarray, step_ms: int,
                    range_ms: int) -> np.ndarray:
    """extrapolatedRate at every scrape time for samples every
    ``step_ms``, in float64; ``vals`` [steps, S] with NaN where a series
    has no sample; returns [S, steps], NaN where a window holds fewer
    than two samples of the series.  The window of scrape j is the
    scrapes in (j*step - range, j*step]."""
    vals = np.asarray(vals, dtype=np.float64)
    n_steps, series = vals.shape
    out = np.full((series, n_steps), np.nan)
    back = (range_ms - 1) // step_ms     # scrapes before j inside the window
    col = np.arange(series)
    range_s = range_ms / 1000.0
    for j in range(n_steps):
        j0 = max(j - back, 0)
        w = vals[j0:j + 1]
        here = ~np.isnan(w)
        cnt = here.sum(axis=0)
        first_i = np.argmax(here, axis=0)
        last_i = len(w) - 1 - np.argmax(here[::-1], axis=0)
        first_v, last_v = w[first_i, col], w[last_i, col]
        # what every fall inside the window took away is added back
        fallen = np.zeros(series)
        prev = np.full(series, np.nan)
        for k in range(len(w)):
            fallen += np.where(here[k] & (w[k] < prev), prev, 0.0)
            prev = np.where(here[k], w[k], prev)
        delta = last_v - first_v + fallen
        t = j * step_ms
        first_t = (j0 + first_i) * step_ms
        last_t = (j0 + last_i) * step_ms
        sampled = (last_t - first_t) / 1000.0
        with np.errstate(divide="ignore", invalid="ignore"):
            avg = sampled / (cnt - 1)
            to_start = (first_t - (t - range_ms)) / 1000.0
            to_end = (t - last_t) / 1000.0
            to_start = np.where(to_start >= avg * 1.1, avg / 2, to_start)
            to_end = np.where(to_end >= avg * 1.1, avg / 2, to_end)
            to_zero = np.where(delta > 0, sampled * (first_v / delta), np.inf)
            start = np.minimum(to_start, to_zero)
            rate = delta * (sampled + start + to_end) / sampled / range_s
        out[:, j] = np.where(cnt >= 2, rate, np.nan)
    return out


def bucket_quantile(q: float, bounds, counts) -> float:
    """Prometheus's ``bucketQuantile`` over one histogram's buckets
    (ascending distinct ``bounds``, cumulative ``counts``; a NaN count is
    a bucket absent at that time), in float64, with ``RANK_TOLERANCE``."""
    have = [(b, c) for b, c in zip(bounds, counts) if not math.isnan(c)]
    if not have or not math.isinf(have[-1][0]) or len(have) < 2:
        return math.nan
    mono, top = [], -math.inf
    for _b, c in have:                    # forced monotonic
        top = max(top, c)
        mono.append(top)
    total = mono[-1]
    if total == 0:
        return math.nan
    rank = q * total
    b = next((i for i in range(len(have) - 1)
              if mono[i] >= rank - RANK_TOLERANCE * total), len(have) - 1)
    if b == len(have) - 1:
        return have[-2][0]
    if mono[b] < rank:         # within the tolerance above bucket b's count
        if mono[b + 1] == mono[b] or math.isinf(have[b + 1][0]):
            return have[b][0]
        frac = min((rank - mono[b]) / (mono[b + 1] - mono[b]), 1.0)
        return have[b][0] + (have[b + 1][0] - have[b][0]) * frac
    if b == 0 and have[0][0] <= 0:
        return have[0][0]
    start = 0.0 if b == 0 else have[b - 1][0]
    below = 0.0 if b == 0 else mono[b - 1]
    frac = min((rank - below) / (mono[b] - below), 1.0)
    return start + (have[b][0] - start) * frac


def fold(per: np.ndarray, resource: np.ndarray, bucket: np.ndarray,
         resources: int, bounds, q: float = Q) -> np.ndarray:
    """[resources, steps]: the float64 sum by (resource, bucket) of the
    per-series rates ``per`` [S, steps] (NaN where no series of a bucket
    has a value), each resource's bucketQuantile at every step, and
    ``> 0`` (NaN where it fails)."""
    k, steps = len(bounds), per.shape[1]
    counts = np.full((resources, k, steps), np.nan)
    has = ~np.isnan(per)
    for r in range(resources):
        for b in range(k):
            rows = (resource == r) & (bucket == b)
            if rows.any():
                counts[r, b] = np.where(
                    has[rows].any(axis=0),
                    np.where(has[rows], per[rows], 0.0).sum(axis=0), np.nan)
    out = np.full((resources, steps), np.nan)
    for r in range(resources):
        for j in range(steps):
            out[r, j] = bucket_quantile(q, bounds, counts[r, :, j])
    with np.errstate(invalid="ignore"):
        return np.where(out > 0, out, np.nan)


def _table(cell, cls: str, lower=None) -> np.ndarray:
    """[resources, steps] at every scrape time, once a run and class."""
    key = f"k8s_apiserver_quantile.table.{cls}" + (
        "" if lower is None else f".{lower.__module__}.{lower.__name__}")
    if key not in cell.cache:
        p, data = cell.params, cell.data
        rows = _matched(cell, cls)
        vals = data["values"][cell.ds.TABLE][:, rows]
        if lower is not None:     # the samples, and nothing else
            vals = np.asarray(lower(vals), dtype=np.float64)
        per = rate_at_scrapes(vals, p["interval_s"] * 1000, RANGE_S * 1000)
        cell.cache[key] = fold(per, data["resource"][rows],
                               data["bucket"][rows], p["resources"],
                               cell.ds.BOUNDS)
    return cell.cache[key]


def _steps(cell, req: dict) -> np.ndarray:
    interval = cell.params["interval_s"]
    t0_s = cell.ds.T0 // 1000
    return np.arange((req["start_s"] - t0_s) // interval,
                     (req["end_s"] - t0_s) // interval + 1)


def reference(cell, req: dict, lower=None):
    at = _steps(cell, req)
    by_res = _table(cell, req["class"], lower)[:, at]
    eval_ms = cell.ds.T0 + at.astype(np.int64) * (
        cell.params["interval_s"] * 1000)
    n = by_res.shape[0]
    keys = np.stack([np.repeat(np.arange(n, dtype=np.int64), len(at)),
                     np.tile(eval_ms, n)], axis=1)
    vals_out = by_res.reshape(-1, 1)
    keep = ~np.isnan(vals_out[:, 0])  # Prometheus leaves such points out
    return keys[keep], vals_out[keep]


def parse(req: dict, reply: bytes):
    body = json.loads(reply)
    if body.get("status") != "success":
        raise ValueError(f"promql failed: {str(body)[:300]}")
    keys, vals = [], []
    for series in body["data"]["result"]:
        if set(series["metric"]) != {"resource"}:
            raise ValueError(f"labels {sorted(series['metric'])}")
        r = req["resources"][series["metric"]["resource"]]
        for t, v in series["values"]:
            keys.append((r, int(round(float(t) * 1000))))
            vals.append(float(v))
    keys = np.array(keys, dtype=np.int64).reshape(-1, 2)
    vals = np.array(vals, dtype=np.float64).reshape(-1, 1)
    order = np.lexsort((keys[:, 1], keys[:, 0]))
    return keys[order], vals[order]


def needed_bytes(cell, req: dict) -> int:
    """A range vector has no bucket: 16 B (i64 timestamp, f64 value) for
    each sample the class's series have in (start - range, end], plus the
    result written once (8 B a timestamp, 8 B a value)."""
    at = _steps(cell, req)
    back = (RANGE_S - 1) // cell.params["interval_s"]
    window = at[-1] + 1 - max(at[0] - back, 0)
    series = int(_matched(cell, req["class"]).sum())
    return 16 * series * window + 16 * cell.params["resources"] * len(at)
