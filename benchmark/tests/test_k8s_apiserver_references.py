"""``k8s_apiserver_quantile``'s reference against a loop over samples at
rehearsal size (the data holds a replica's restart and counters past
2^24), its pieces on hand-worked series (a restart, a non-monotonic
window, a tie), what ``lower`` rounds, the data set's own promises at
full size, ``needed_bytes`` by hand, and a whole run of the cell with the
timed path broken underneath: an altered value and a lost write read
``correct: false``."""

import io
import math

import numpy as np
import pytest
import run

CONFIG = "prom-apiserver-116k"
WORKLOAD = "apiserver116k.resource_p99"
MIX = "k8s_apiserver_slo"


def _cell(seed: int = 11, rehearse: bool = True):
    config = run.load_json(run.HERE, "configs", f"{CONFIG}.json")
    return run.new_cell(config, rehearse=rehearse, seed=seed)


def _family():
    mix = run.load_json(run.HERE, "traffic", f"{MIX}.json")
    return mix, run.load_module("queries", mix["family"])


def _rate_of_points(pts, t, range_s):
    """Prometheus's extrapolatedRate of one series' (time, value) points
    inside (t - range, t], one by one; None under two points."""
    if len(pts) < 2:
        return None
    delta = pts[-1][1] - pts[0][1]
    for (_t0, a), (_t1, b) in zip(pts, pts[1:]):
        if b < a:
            delta += a
    sampled = pts[-1][0] - pts[0][0]
    avg = sampled / (len(pts) - 1)
    to_start = pts[0][0] - (t - range_s)
    to_end = t - pts[-1][0]
    if to_start >= 1.1 * avg:
        to_start = avg / 2
    if to_end >= 1.1 * avg:
        to_end = avg / 2
    if delta > 0:
        to_start = min(to_start, sampled * pts[0][1] / delta)
    return delta * (sampled + to_start + to_end) / sampled / range_s


def _quantile(q, buckets, tol):
    """bucketQuantile over {bound: count}, written out once more."""
    bounds = sorted(buckets)
    if len(bounds) < 2 or bounds[-1] != math.inf:
        return math.nan
    counts = list(np.maximum.accumulate([buckets[b] for b in bounds]))
    if counts[-1] == 0:
        return math.nan
    rank = q * counts[-1]
    for b in range(len(bounds) - 1):
        if counts[b] >= rank - tol * counts[-1]:
            break
    else:
        return bounds[-2]
    if counts[b] < rank:       # within the tolerance above bucket b's count
        up_b, up_c = bounds[b + 1], counts[b + 1]
        if up_c == counts[b] or up_b == math.inf:
            return bounds[b]
        return bounds[b] + (up_b - bounds[b]) * min(
            (rank - counts[b]) / (up_c - counts[b]), 1.0)
    if b == 0 and bounds[0] <= 0:
        return bounds[0]
    lo_b, lo_c = (0.0, 0.0) if b == 0 else (bounds[b - 1], counts[b - 1])
    return lo_b + (bounds[b] - lo_b) * min((rank - lo_c)
                                           / (counts[b] - lo_c), 1.0)


@pytest.mark.parametrize("seed", [11, 2147483901])
def test_reference_is_the_brute_force_loop(seed):
    cell = _cell(seed)
    mix, family = _family()
    traffic = run.Traffic(family, cell, mix, seed=seed, stream=1)
    p, ds, data = cell.params, cell.ds, cell.data
    v = data["values"][ds.TABLE]
    t0 = ds.T0 // 1000
    r_step = data["restart"][1]
    for _ in range(2):
        req = traffic.next()
        keys, vals = family.reference(cell, req)
        got = {tuple(k): x for k, x in zip(keys.tolist(), vals[:, 0])}
        rows = np.flatnonzero(family._matched(cell, req["class"]))
        want = {}
        for t in range(req["start_s"], req["end_s"] + 1, 5 * p["interval_s"]):
            buckets = {}
            for s in rows:
                pts = [(t0 + k * p["interval_s"], v[k, s])
                       for k in range(ds.steps(p))
                       if t - family.RANGE_S < t0 + k * p["interval_s"] <= t]
                rate = _rate_of_points(pts, t, family.RANGE_S)
                if rate is not None:
                    key = (int(data["resource"][s]),
                           ds.BOUNDS[data["bucket"][s]])
                    buckets[key] = buckets.get(key, 0.0) + rate
            for r in range(p["resources"]):
                mine = {b: c for (rr, b), c in buckets.items() if rr == r}
                x = _quantile(family.Q, mine, family.RANK_TOLERANCE)
                if x > 0:
                    want[(r, t * 1000)] = x
        sub = {k: x for k, x in got.items()
               if (k[1] // 1000 - req["start_s"]) % (5 * p["interval_s"]) == 0}
        assert len(want) > 0 and sorted(sub) == sorted(want)
        for k in want:
            assert sub[k] == pytest.approx(want[k], rel=1e-12)
    # the restart lies inside the data's hour, as the windows read it
    assert 2 <= r_step < ds.steps(p) - 1


def test_rate_and_fold_hand_worked():
    _mix, family = _family()
    # a restart: 100, 110, then the process starts again at 0, 10
    out = family.rate_at_scrapes(np.array([[100.0], [110.0], [0.0], [10.0]]),
                                 30_000, 300_000)
    # t = 90 s, window (-210 s, 90 s]: the increase is 10 - 100 + 110 (what
    # fell is added back) = 20 over 90 s sampled; 210 s to the window's
    # start is over 1.1 x 30 s: half an interval
    assert out[0, 3] == pytest.approx(20 * (90 + 15) / 90 / 300, rel=1e-12)
    bounds = (0.1, 0.5, 1.0, math.inf)
    # non-monotonic buckets (a rate's extrapolation differs by bucket):
    # 3, 2, 4, 4 is read as 3, 3, 4, 4; rank 3.5 falls in (0.5, 1]
    per = np.array([[3.0], [2.0], [4.0], [4.0]])
    res, bucket = np.zeros(4, np.int64), np.arange(4)
    got = family.fold(per, res, bucket, 1, bounds, q=0.875)
    assert got[0, 0] == pytest.approx(0.5 + 0.5 * 0.5 / 1.0)
    # a tie: q x total equals a bucket's count, an empty bucket above it;
    # the tie rule takes the lower bucket
    per = np.array([[99.0], [99.0], [99.0], [100.0]]) / 270.0
    assert family.fold(per, res, bucket, 1, bounds)[0, 0] == pytest.approx(
        0.1)
    # no +Inf bucket, or no observations: nothing, and > 0 drops it
    assert np.isnan(family.fold(per[:3], res[:3], bucket[:3], 1, bounds[:3]))
    assert np.isnan(family.fold(0 * per, res, bucket, 1, bounds))
    # the highest finite bound where the rank falls in +Inf
    per = np.array([[1.0], [1.0], [1.0], [10.0]])
    assert family.fold(per, res, bucket, 1, bounds)[0, 0] == 1.0
    # a bucket absent at that time is left out: 1, 2, 9 over 0.1, 1, +Inf
    assert family.bucket_quantile(0.5, bounds, [1.0, math.nan, 2.0, 9.0]) == (
        pytest.approx(1.0))


def test_lower_rounds_the_samples_and_nothing_else():
    """Past 2^24 a float32 sample has steps of 2 and more: the busiest
    counters' increases move, and so do the answers; the exact reference
    is unmoved by a float64 'lower'."""
    cell = _cell()
    _mix, family = _family()
    t0 = cell.ds.T0 // 1000
    for cls in family.CLASSES:
        req = {"class": cls, "start_s": t0 + 40 * 30, "end_s": t0 + 100 * 30,
               "resources": {}}
        keys, exact = family.reference(cell, req)
        k64, same = family.reference(cell, req, lambda x: np.asarray(x))
        assert np.array_equal(keys, k64) and np.array_equal(exact, same)
    req = {"class": "read_p99", "start_s": t0 + 40 * 30,
           "end_s": t0 + 100 * 30, "resources": {}}
    keys, exact = family.reference(cell, req)
    k32, rounded = family.reference(cell, req, family.F32)
    assert np.array_equal(keys, k32)
    err = np.abs(rounded - exact) / np.maximum(np.abs(exact), 1e-3)
    assert err.max() > family.LIMITS["read_p99"]


def test_data_set_keeps_its_promises():
    import pyarrow as pa

    cell = _cell(rehearse=False)
    ds, p, data = cell.ds, cell.params, cell.data
    v = data["values"][ds.TABLE]
    assert v.shape == (120, 116_424) and ds.rows(p) == 13_970_880
    assert ds.n_series(p) == 116_424 == 3 * 1764 * 22
    assert len(ds.label_sets(p)) == 1764
    assert data["read"].sum() == 36_960 and data["write"].sum() == 79_200
    assert not (data["read"] & data["write"]).any()
    assert len(set(data["resource"][data["read"]])) == 200
    assert len(set(data["resource"][data["write"]])) == 200
    # whole requests, cumulative over le at every scrape, +Inf last
    assert (v % 1 == 0).all() and (v >= 0).all()
    by_le = v.reshape(120, 3 * 1764, 22)
    assert (np.diff(by_le, axis=2) >= 0).all()
    assert ds.LE[-1] == "+Inf" and len(ds.BOUNDS) == 22
    # the busiest counters pass 2^24 on every replica, all under 2^40
    per_replica = v.reshape(120, 3, -1)
    assert (per_replica[0].max(axis=1) > 2.0 ** 24).all()
    assert v.max() < 2.0 ** 40
    # one replica restarts: all its counters read 0 at one scrape
    r_rep, r_step = data["restart"]
    assert (per_replica[r_step, r_rep] == 0).all()
    falls = np.diff(v, axis=0) < 0
    assert set(np.flatnonzero(falls.any(axis=1))) == {r_step - 1}
    assert set(data["replica"][falls.any(axis=0)]) == {r_rep}
    # the same seed, the same data
    again = ds.generate(11, p)
    assert np.array_equal(again["values"][ds.TABLE], v)
    # rehearsal bodies: every row sent, ten dictionary tags
    small = _cell()
    sent = 0
    for table, body, n in small.ds.arrow_bodies(small.data, small.params):
        got = pa.ipc.open_stream(io.BytesIO(body)).read_all()
        assert table == ds.TABLE and got.num_rows == n
        assert got.column_names == ds.TAGS + ["ts", "greptime_value"]
        sent += n
    assert sent == small.ds.rows(small.params)


def test_full_size_counts():
    config = run.load_json(run.HERE, "configs", f"{CONFIG}.json")
    ds = run.load_module("datasets", config["dataset"])
    p = config["params"]
    s = config["series"]
    assert len(ds.label_sets(p)) == s["label_sets_a_replica"] == 1764
    assert ds.n_series(p) == s["in_the_table"] == 116_424
    assert len(ds.BOUNDS) == s["buckets"] == 22
    assert s["read_padded"] == 1 << 16 > s["read_matched"] == 36_960
    assert s["write_padded"] == 1 << 17 > s["write_matched"] == 79_200
    assert len(ds.resources(p)) == s["groups"] == 200
    assert len({r[2] for r in ds.resources(p)}) == 200
    assert sum(r[3] for r in ds.resources(p)) == p["namespaced"] == 160
    assert list(config["published"]["bounds_s"]) == list(ds.BOUNDS[:-1])
    assert {k: tuple(v.values()) for k, v in config["latency"].items()} == (
        ds.LATENCY)
    assert config["sample_precision"] == "float64"
    assert config["compute_precision"] == "float32"


def test_needed_bytes_hand_worked():
    cell = _cell()
    _mix, family = _family()
    ds, data = cell.ds, cell.data
    t0 = ds.T0 // 1000
    # 30 min ending at scrape 100: evaluation scrapes 40..100, the first
    # window reaches back to scrape 31: 70 samples of every matched series
    # at 16 B, and 10 resources x 61 points x 16 B
    for cls in family.CLASSES:
        req = {"class": cls, "start_s": t0 + 40 * 30, "end_s": t0 + 100 * 30}
        matched = int(family._matched(cell, cls).sum())
        assert family.needed_bytes(cell, req) == (
            16 * 70 * matched + 16 * 10 * 61)


# ---------------------------------------------------------------------------
# a whole run of the cell, sound and broken (test_faults.py's pattern)
# ---------------------------------------------------------------------------

def _run():
    return run.run_cell(WORKLOAD, seed=7, seconds=1.0, trace=False,
                        rehearse=True, need_tpu=False)


def _over(res) -> list[str]:
    return [k for k, c in res["checks"].items() if c["value"] > c["limit"]]


def test_sound_run_is_correct():
    res = _run()
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert {"max_err.read_p99", "max_err.write_p99"} <= set(res["checks"])


def test_altered_value_is_not_correct(monkeypatch):
    """Every reply after the warm-up carries one point moved by 0.1 %."""
    import copy

    from greptimedb_tpu.promql import format as prom_format

    real, calls = prom_format.range_payload, {"n": 0}

    def altered(*a, **kw):
        out = copy.deepcopy(real(*a, **kw))
        calls["n"] += 1
        result = out.get("data", {}).get("result")
        if calls["n"] > 2 and result:
            t, x = result[0]["values"][0]
            result[0]["values"][0] = [t, repr(float(x) * 1.001 + 1e-3)]
        return out

    monkeypatch.setattr(prom_format, "range_payload", altered)
    res = _run()
    assert not res["correct"] and res["failed"] > 0, res["checks"]
    assert any(k.startswith("max_err.") for k in _over(res)), res["checks"]


def test_lost_write_is_not_correct(monkeypatch):
    """The server acknowledges a body's rows and writes none of them."""
    from greptimedb_tpu.servers import http

    monkeypatch.setattr(http, "_ingest_columns",
                        lambda db, table, cols, *a, **kw: len(cols["ts"]))
    res = _run()
    assert not res["correct"], res["checks"]
    assert res["checks"]["rows_not_counted_back"]["value"] > 0


def test_a_program_without_the_fold_exits_at_load(monkeypatch):
    """A program whose ``promql.engine`` has no ``histogram_fold`` stops the
    run when the family loads: an error exit, before any data is made."""
    from greptimedb_tpu.promql import engine

    monkeypatch.delattr(engine, "histogram_fold")
    with pytest.raises(SystemExit) as e:
        _family()
    assert "histogram_fold" in str(e.value.code)
