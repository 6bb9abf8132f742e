"""PromQL ``sum by (instance)(rate(node_cpu_seconds_total{mode="<m>"}[5m]))``
through ``GET /v1/prometheus/api/v1/query_range``: one hour at a 60 s
step, the hour's end drawn from the seed inside the last six hours on a
scrape boundary.  One class, ``mode_rate``; its ``mode`` is the entry's.

The reference is Prometheus's ``extrapolatedRate`` (counter semantics,
window (t - range, t]) in numpy over the generated samples; no jax,
nothing of the program under test.  Stored DOUBLEs compute in float32 on
the device, so the reference reads the samples at float32 and then works
in float64.  With ``lower`` (lowprec.py) it is the control: samples and
result rounded, arithmetic in float32.

With less data than 2 h (a rehearsal) the queried span is half of it.
"""

from __future__ import annotations

import json
import urllib.parse

import numpy as np

ROUTE = "/v1/prometheus/api/v1/query_range"
RANGE_S = 300
STEP_S = 60
_SPAN_S = 3600
_END_WITHIN_S = 6 * 3600
# |got - ref| / max(|ref|, SCALE): rates are CPU-seconds a second, summed
# over an instance's CPUs; the rarest modes run near 0.01
SCALE = {"mode_rate": 1e-3}
# limit on that error, set from chip readings (PERF.md section 2)
LIMITS = {"mode_rate": 1e-4}


def _span(cell) -> tuple[int, int]:
    """(seconds a query spans, seconds before the data's end in which it
    may end), both whole scrape intervals."""
    total = cell.params["hours"] * 3600
    span = min(_SPAN_S, total // 2)
    return span, min(_END_WITHIN_S, total - span - RANGE_S)


def request(cell, mix: dict, entry: dict, rng):
    if entry["class"] != "mode_rate":
        raise ValueError(f"prom_rate has no class {entry['class']!r}")
    mode = entry["params"]["mode"]
    ds, interval = cell.ds, cell.params["interval_s"]
    span, within = _span(cell)
    last = ds.steps(cell.params) - 1
    e = last - int(rng.integers(within // interval))
    end_s = ds.T0 // 1000 + e * interval
    query = (f'sum by (instance)(rate({ds.TABLE}{{mode="{mode}"}}'
             f'[{RANGE_S}s]))')
    qs = urllib.parse.urlencode({"query": query, "start": end_s - span,
                                 "end": end_s, "step": STEP_S})
    return {"class": "mode_rate", "method": "GET", "path": f"{ROUTE}?{qs}",
            "route": ROUTE, "mode": mode, "start_s": end_s - span,
            "end_s": end_s}


def _matched(cell, mode: str) -> np.ndarray:
    modes = cell.ds.MODES
    return np.arange(modes.index(mode), cell.ds.n_series(cell.params),
                     len(modes))


def rate(vals: np.ndarray, t0_ms: int, step_ms: int, eval_ms: np.ndarray,
         range_ms: int, dtype=np.float64) -> np.ndarray:
    """extrapolatedRate for samples every ``step_ms`` from ``t0_ms``;
    ``vals`` [steps, S]; returns [S, len(eval_ms)], NaN where a window
    holds fewer than two samples."""
    steps, series = vals.shape
    vals = vals.astype(dtype)
    out = np.full((series, len(eval_ms)), np.nan, dtype=dtype)
    for i, t in enumerate(eval_ms):
        t = int(t)
        lo = max((t - range_ms - t0_ms) // step_ms + 1, 0)
        hi = min((t - t0_ms) // step_ms, steps - 1)
        n = hi - lo + 1
        if n < 2:
            continue
        w = vals[lo:hi + 1]
        d = np.diff(w, axis=0)
        delta = w[-1] - w[0] + np.where(d < 0, w[:-1], 0).sum(axis=0)
        first_t, last_t = t0_ms + lo * step_ms, t0_ms + hi * step_ms
        sampled = dtype((last_t - first_t) / 1000.0)
        avg = sampled / (n - 1)
        to_start = dtype((first_t - (t - range_ms)) / 1000.0)
        to_end = dtype((t - last_t) / 1000.0)
        if to_start >= avg * dtype(1.1):
            to_start = avg / 2
        if to_end >= avg * dtype(1.1):
            to_end = avg / 2
        with np.errstate(divide="ignore", invalid="ignore"):
            to_zero = np.where(delta > 0, sampled * (w[0] / delta), np.inf)
        start = np.minimum(to_start, to_zero)
        out[:, i] = (delta * (sampled + start + to_end) / sampled
                     / dtype(range_ms / 1000.0))
    return out


def reference(cell, req: dict, lower=None):
    ds, p = cell.ds, cell.params
    eval_ms = np.arange(req["start_s"], req["end_s"] + 1, STEP_S,
                        dtype=np.int64) * 1000
    vals = cell.data["values"][:, _matched(cell, req["mode"])]
    if lower is None:
        per = rate(vals.astype(np.float32), ds.T0, p["interval_s"] * 1000,
                   eval_ms, RANGE_S * 1000)
    else:
        per = rate(lower(vals), ds.T0, p["interval_s"] * 1000, eval_ms,
                   RANGE_S * 1000, dtype=np.float32)
    by_inst = per.reshape(p["instances"], p["cpus"], len(eval_ms)).sum(axis=1)
    if lower is not None:
        by_inst = lower(by_inst)
    keys = np.stack([np.repeat(np.arange(p["instances"], dtype=np.int64),
                               len(eval_ms)),
                     np.tile(eval_ms, p["instances"])], axis=1)
    vals_out = by_inst.reshape(-1, 1).astype(np.float64)
    keep = ~np.isnan(vals_out[:, 0])  # Prometheus leaves such points out
    return keys[keep], vals_out[keep]


def parse(req: dict, reply: bytes):
    body = json.loads(reply)
    if body.get("status") != "success":
        raise ValueError(f"promql failed: {str(body)[:300]}")
    keys, vals = [], []
    for series in body["data"]["result"]:
        inst = int(series["metric"]["instance"].split("-")[1].split(":")[0])
        for t, v in series["values"]:
            keys.append((inst, int(round(float(t) * 1000))))
            vals.append(float(v))
    keys = np.array(keys, dtype=np.int64).reshape(-1, 2)
    vals = np.array(vals, dtype=np.float64).reshape(-1, 1)
    order = np.lexsort((keys[:, 1], keys[:, 0]))
    return keys[order], vals[order]


def needed_bytes(cell, req: dict) -> int:
    """A range vector has no bucket: 12 B (i64 timestamp, f32 value) for
    each sample of the matched series in (start - range, end], plus the
    result written once (8 B a timestamp, 4 B a value)."""
    p = cell.params
    samples = (req["end_s"] - req["start_s"] + RANGE_S) // p["interval_s"]
    matched = p["instances"] * p["cpus"]
    points = p["instances"] * ((req["end_s"] - req["start_s"]) // STEP_S + 1)
    return 12 * matched * samples + 12 * points
