"""``k8s_rate``'s reference against a loop over samples at rehearsal size
(the generated data holds restarts and replaced pods), its pieces on
hand-worked series, the data set's own promises, and ``needed_bytes``
by hand."""

import io

import numpy as np
import pytest
import run

CONFIG = "prom-k8s-100k"
MIX = "k8s_cluster_cpu"


def _cell(seed: int = 11):
    config = run.load_json(run.HERE, "configs", f"{CONFIG}.json")
    return run.new_cell(config, rehearse=True, seed=seed)


def _family_and_requests(cell, n: int):
    mix = run.load_json(run.HERE, "traffic", f"{MIX}.json")
    family = run.load_module("queries", mix["family"])
    traffic = run.Traffic(family, cell, mix, seed=11, stream=1)
    return family, [traffic.next() for _ in range(n)]


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


@pytest.mark.parametrize("seed", [11, 12])
def test_reference_is_the_brute_force_loop(seed):
    cell = _cell(seed)
    family, reqs = _family_and_requests(cell, 6)
    p, ds, data = cell.params, cell.ds, cell.data
    v32 = data["values"].astype(np.float32).astype(np.float64)
    image, images = data["tags"]["image"]
    t0 = ds.T0 // 1000
    seen = {"reset": 0, "late": 0, "early": 0, "one_sample": 0}
    for req in reqs:
        keys, vals = family.reference(cell, req)
        got = {tuple(k): x for k, x in zip(keys.tolist(), vals[:, 0])}
        want = {}
        for t in range(req["start_s"], req["end_s"] + 1, p["interval_s"]):
            for s in range(v32.shape[1]):
                if images[image[s]] == "":
                    continue   # image!="" drops the pod-level cgroups
                pts = [(t0 + k * p["interval_s"], v32[k, s])
                       for k in range(ds.steps(p))
                       if t - family.RANGE_S < t0 + k * p["interval_s"] <= t
                       and not np.isnan(v32[k, s])]
                seen["one_sample"] += len(pts) == 1
                seen["reset"] += any(b < a for (_x, a), (_y, b)
                                     in zip(pts, pts[1:]))
                if len(pts) >= 2:
                    seen["late"] += (pts[0][0] - p["interval_s"]
                                     > t - family.RANGE_S)
                    seen["early"] += pts[-1][0] < t
                r = _rate_of_points(pts, t, family.RANGE_S)
                if r is not None:
                    key = (int(data["namespace"][s]), t * 1000)
                    want[key] = want.get(key, 0.0) + r
        assert sorted(got) == sorted(want)
        for k in want:
            assert got[k] == pytest.approx(want[k], rel=1e-9)
    # the rehearsal data exercises every case the issue names
    assert all(seen.values()), seen


def test_rate_at_scrapes_hand_worked():
    family = run.load_module("queries", "k8s_rate")
    nan = np.nan
    vals = np.array([
        # a reset after 20; starts late; ends early; one sample; empty
        [10.0, nan, 3.0, nan, nan],
        [20.0, nan, 6.0, nan, nan],
        [5.0, 4.0, nan, nan, nan],
        [15.0, 9.0, nan, 7.0, nan],
    ])
    out = family.rate_at_scrapes(vals, 15_000, 60_000)
    assert out.shape == (5, 4)
    # scrape 3 (t = 45 s), window (-15 s, 45 s]: all four scrapes
    # increase 10 + 5 + 10 = 25 over 45 s sampled; 15 s to the window's
    # start is under 1.1 x 15 s and the time to zero is 45 * 10 / 25 = 18 s
    assert out[0, 3] == pytest.approx(25 * (45 + 15) / 45 / 60)
    # two samples, at 30 s and 45 s: 5 over 15 s; 45 s to the window's start
    # is extrapolated by half an interval, the time to zero 15 * 4 / 5 = 12 s
    # is more than that
    assert out[1, 3] == pytest.approx(5 * (15 + 7.5) / 15 / 60)
    # ended at 15 s: 3 over 15 s, 30 s to the window's end -> half an
    # interval, 15 s to its start, time to zero 15 * 3 / 3 = 15 s
    assert out[2, 3] == pytest.approx(3 * (15 + 15 + 7.5) / 15 / 60)
    assert np.isnan(out[3, 3]) and np.isnan(out[4]).all()
    # scrape 0 has one sample of every series: no rate anywhere
    assert np.isnan(out[:, 0]).all()
    # group sums leave out what has no value; an empty group has none
    total = family.group_sum(out, np.array([0, 0, 1, 1, 2]), 4)
    assert total[0, 3] == pytest.approx(out[0, 3] + out[1, 3])
    assert total[1, 3] == pytest.approx(out[2, 3])
    assert np.isnan(total[2]).all() and np.isnan(total[3]).all()


def test_window_is_left_open():
    """A sample exactly ``range`` before the evaluation time is outside."""
    family = run.load_module("queries", "k8s_rate")
    vals = np.arange(1.0, 13.0)[:, None] * 30.0   # one core, every 30 s
    out = family.rate_at_scrapes(vals, 30_000, 300_000)
    # scrape 11: samples 2..11 (ten of them, 270 s sampled), not sample 1
    assert out[0, 11] == pytest.approx(270 * (270 + 30 + 0) / 270 / 300)


def test_data_set_keeps_its_promises():
    import pyarrow as pa

    cell = _cell()
    ds, p, data = cell.ds, cell.params, cell.data
    v = data["values"]
    assert v.shape == (ds.steps(p), ds.n_series(p))
    has = ~np.isnan(v)
    assert int(has.sum()) == ds.rows(p)
    assert (has.sum(axis=1) == ds.live_series(p)).all()
    assert len(data["matched"]) == ds.matched_series(p)
    image, images = data["tags"]["image"]
    assert sorted(data["matched"]) == [
        s for s in range(v.shape[1]) if images[image[s]] != ""]
    # a life is one stretch of scrapes; replaced pods' series end early and
    # their successors start late, in the same namespace
    lives = np.diff(has.astype(np.int8), axis=0)
    assert (np.abs(lives).sum(axis=0) <= 1).all()
    late = np.flatnonzero(~has[0])
    early = np.flatnonzero(~has[-1])
    assert len(late) == len(early) == ds.replaced_pods(p) // 2 * 5
    # restarts: the only falls, one a series, and none in a pod's cgroup
    falls = np.diff(v, axis=0) < 0   # NaN compares false
    assert falls.sum() == max(
        1, round(len(data["matched"]) * ds.RESTART_SHARE))
    assert (falls.sum(axis=0) <= 1).all()
    cgroups = np.setdiff1d(np.arange(v.shape[1]), data["matched"])
    assert not falls[:, cgroups].any()
    assert np.nanmax(v[:, data["matched"]]) < 65536 and np.nanmax(v) < 131072
    assert ds.namespace_pods(p).sum() == p["pods"]
    assert (np.diff(ds.namespace_pods(p)) <= 0).all()
    # ten tags, each a dictionary column; absent samples are not sent
    assert list(data["tags"]) == ds.TAGS and len(ds.TAGS) == 10
    sent = 0
    for _table, body, n in ds.arrow_bodies(data, p):
        got = pa.ipc.open_stream(io.BytesIO(body)).read_all()
        assert got.num_rows == n and not np.isnan(
            got.column("greptime_value").to_numpy()).any()
        assert got.column_names == ds.TAGS + ["ts", "greptime_value"]
        sent += n
    assert sent == ds.rows(p)
    # the same seed, the same data
    again = ds.generate(11, p)
    assert np.array_equal(again["values"], v, equal_nan=True)
    assert again["tags"]["pod"][1] == data["tags"]["pod"][1]


def test_full_size_counts():
    """What the configuration's file says of the series, from the data
    set's own arithmetic (no data made)."""
    config = run.load_json(run.HERE, "configs", f"{CONFIG}.json")
    ds = run.load_module("datasets", config["dataset"])
    p = config["params"]
    assert ds.live_series(p) == config["series"]["live"] == 100_000
    assert ds.n_series(p) == config["series"]["in_table"] == 105_000
    assert ds.matched_series(p) == config["series"]["matched"] == 63_000
    assert ds.rows(p) == 12_000_000
    assert p["namespaces"] == config["series"]["groups"]


def test_needed_bytes_hand_worked():
    cell = _cell()
    family = run.load_module("queries", "k8s_rate")
    ds, p, data = cell.ds, cell.params, cell.data
    t0 = ds.T0 // 1000
    # 30 min ending at scrape 100: evaluation scrapes 40..100, the first
    # window reaches back to scrape 31: scrapes 31..100 of every matched
    # series that has them, 12 B each; 5 namespaces x 61 points x 12 B
    req = {"class": "namespace_cpu", "start_s": t0 + 40 * 30,
           "end_s": t0 + 100 * 30}
    has = ~np.isnan(data["values"][31:101][:, data["matched"]])
    assert family.needed_bytes(cell, req) == 12 * int(has.sum()) + 12 * 5 * 61
    # with every series alive throughout it would be 70 samples a series
    assert int(has.sum()) < 70 * len(data["matched"])
