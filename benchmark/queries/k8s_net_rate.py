"""The "Receive Bandwidth" and "Transmit Bandwidth" panels of
kubernetes-mixin's ``k8s-resources-cluster`` dashboard over cAdvisor's
byte counters:

    sum by (namespace)(rate(container_network_receive_bytes_total{job="cadvisor",namespace=~".+"}[5m]))
    sum by (namespace)(rate(container_network_transmit_bytes_total{job="cadvisor",namespace=~".+"}[5m]))

through ``GET /v1/prometheus/api/v1/query_range``.  What the README's
table would say of this family:

| class | parameters | reply |
| --- | --- | --- |
| ``receive_bandwidth`` | none of its own: 30 min at a 30 s step (61 steps; half the data where there is less than an hour), the end drawn from the seed on a scrape boundary inside the last 25 min (50 ends) | one series a namespace, 61 points each |
| ``transmit_bandwidth`` | the same over the other table | the same |

The matchers and the grouping are the same in every request of a class,
so one program serves it; only ``start`` and ``end`` move.

The reference is Prometheus's ``extrapolatedRate`` (counter semantics,
window (t - range, t]) in numpy, **in float64 over the float64 samples
that were sent**, with counter resets and with each series' own first
and last sample (a series that starts late, ends early, or has one
sample in a window), then a plain float64 sum of the series of each
namespace; no jax, nothing of the program under test, nothing of a
sibling family.  A busy pod's byte counter that is three days old
stands near 1e13 and the fastest near 3e14, where float32 has steps of
1 MB and 32 MB: a program that reads its samples at float32 cannot
answer this deployment, and that is what the limits hold.  With ``lower`` the *samples* are rounded through it before the
difference and everything else stays as it is: ``lower = float32``
rounding (``F32``) is the control that stands for such a program;
``control.py`` hands over the bfloat16 rounding of ``lowprec.py``.

**Memoised, and still the plain reference.**  The step is the scrape
interval and every end lies on a scrape boundary, so every evaluation
time of every request is one of the data's scrape times.  ``_table``
therefore evaluates the same function at every scrape time once a run
and class ([namespaces, steps]) and ``reference`` slices the request's
61 columns out of it: the same arithmetic on the same samples as
evaluating the request alone (``tests/test_k8s_net_references.py`` holds
the slice to a loop over samples), done once where a window of several
hundred replies would do it several hundred times.
"""

from __future__ import annotations

import json
import urllib.parse

import numpy as np

ROUTE = "/v1/prometheus/api/v1/query_range"
RANGE_S = 300
_SPAN_S = 1800
_END_WITHIN_S = 1500
CLASSES = {"receive_bandwidth": "RECEIVE", "transmit_bandwidth": "TRANSMIT"}
# |got - ref| / max(|ref|, SCALE): a namespace's rate is bytes a second,
# from ~1e4 for the smallest namespaces to ~1e9 for the largest
SCALE = {"receive_bandwidth": 1.0, "transmit_bandwidth": 1.0}
# limit on that error, set between two readings (PERF.md section 2 has
# them): sound runs on the chip (exact increases; the error is the f32
# sum of up to 20,382 rates a namespace) read under 1e-5, a float32 value
# column over counters up to three days old (the F32 control in numpy,
# the parent commit on the chip) reads 3e-5 and more in every request,
# the bfloat16 control 1 and more
LIMITS = {"receive_bandwidth": 2e-5, "transmit_bandwidth": 2e-5}


def F32(x):
    """The control's ``lower``: samples as a float32 value column holds
    them."""
    return np.asarray(x).astype(np.float32)


def table_of(cell, cls: str) -> str:
    if cls not in CLASSES:
        raise ValueError(f"k8s_net_rate has no class {cls!r}")
    return getattr(cell.ds, CLASSES[cls])


def _span(cell) -> tuple[int, int]:
    """(seconds a query spans, seconds before the data's end in which it
    may end), both whole scrape intervals."""
    total = cell.ds.steps(cell.params) * cell.params["interval_s"]
    span = min(_SPAN_S, total // 2)
    return span, min(_END_WITHIN_S, total - span - RANGE_S)


def query(cell, cls: str) -> str:
    return (f'sum by (namespace)(rate({table_of(cell, cls)}'
            f'{{job="cadvisor",namespace=~".+"}}[5m]))')


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
    return {"class": cls, "method": "GET", "path": f"{ROUTE}?{qs}",
            "route": ROUTE, "start_s": end_s - span, "end_s": end_s}


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


def group_sum(per: np.ndarray, group: np.ndarray, groups: int) -> np.ndarray:
    """[groups, steps]: the float64 sum over each group's series that
    have a value, NaN where none has."""
    out = np.full((groups, per.shape[1]), np.nan)
    for g in range(groups):
        rows = per[group == g]
        has = ~np.isnan(rows)
        out[g] = np.where(has.any(axis=0),
                          np.where(has, rows, 0.0).sum(axis=0), np.nan)
    return out


def _table(cell, cls: str, lower=None) -> np.ndarray:
    """[namespaces, steps] at every scrape time, once a run and class."""
    key = f"k8s_net_rate.table.{cls}" + ("" if lower is None else ".lower")
    if key not in cell.cache:
        p, data = cell.params, cell.data
        vals = data["values"][table_of(cell, cls)]
        if lower is not None:     # the samples, and nothing else
            vals = np.asarray(lower(vals), dtype=np.float64)
        per = rate_at_scrapes(vals, p["interval_s"] * 1000, RANGE_S * 1000)
        cell.cache[key] = group_sum(per, data["namespace"], p["namespaces"])
    return cell.cache[key]


def _steps(cell, req: dict) -> np.ndarray:
    interval = cell.params["interval_s"]
    t0_s = cell.ds.T0 // 1000
    return np.arange((req["start_s"] - t0_s) // interval,
                     (req["end_s"] - t0_s) // interval + 1)


def reference(cell, req: dict, lower=None):
    at = _steps(cell, req)
    by_ns = _table(cell, req["class"], lower)[:, at]
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
    """A range vector has no bucket: 16 B (i64 timestamp, f64 value) for
    each sample the class's series have in (start - range, end], plus the
    result written once (8 B a timestamp, 8 B a value)."""
    key = f"k8s_net_rate.samples.{req['class']}"
    if key not in cell.cache:
        has = ~np.isnan(cell.data["values"][table_of(cell, req["class"])])
        cell.cache[key] = np.concatenate([[0], np.cumsum(has.sum(axis=1))])
    upto = cell.cache[key]
    at = _steps(cell, req)
    back = (RANGE_S - 1) // cell.params["interval_s"]
    samples = int(upto[at[-1] + 1] - upto[max(at[0] - back, 0)])
    return 16 * samples + 16 * cell.params["namespaces"] * len(at)
